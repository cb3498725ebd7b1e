"""The prefill executable's share of its compute roofline: runs x FLOPs of
one dispatched 512-position chunk (padding through the dense matrices,
real positions through their held experts) over peak, against traced
seconds."""

from answer_reduce import prefill_roofline as read  # noqa: F401
