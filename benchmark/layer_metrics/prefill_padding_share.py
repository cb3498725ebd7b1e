"""Padded over all dispatched prefill positions, over the window (the
model's counter)."""

from answer_reduce import prefill_padding_share as read  # noqa: F401
