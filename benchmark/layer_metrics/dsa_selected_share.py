"""The (query, cached position) pairs attention went over after the
indexer's choice, over those the queries could see, over the window (the
model's counters): 100 means the mechanism kept everything (contexts of at
most index_topk)."""

from dsa_reduce import dsa_selected_share as read  # noqa: F401
