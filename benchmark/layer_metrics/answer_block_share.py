"""Share of the traced stretch the engine's thread is inside
``answer.generate``: what a scheduler that interleaves would give back."""

from answer_reduce import answer_block_share as read  # noqa: F401
