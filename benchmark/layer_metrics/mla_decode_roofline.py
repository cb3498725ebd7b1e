"""The decode executable's share of its byte roofline on the absorbed
path: runs x the bytes a step must move (dense and absorbed matrices, the
experts the batch touched, the latent rows of the live contexts, the
head's rows) over peak bytes/s, against traced seconds."""

from mla_reduce import mla_decode_roofline as read  # noqa: F401
