"""The decode executable's share of its bandwidth roofline: runs x bytes a
step must move (dense weights, experts touched, states, keys/values,
head rows) over peak bytes/s, against traced seconds."""

from answer_reduce import decode_roofline as read  # noqa: F401
