"""Sequences a decode step, over the window (the model's counter)."""

from answer_reduce import decode_batch_occupancy as read  # noqa: F401
