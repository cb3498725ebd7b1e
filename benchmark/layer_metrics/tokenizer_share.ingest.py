"""Seconds inside the ring's ``encoder.tokenize`` spans over the traced
stretch."""

from ring_reduce import tokenizer_share as read  # noqa: F401
