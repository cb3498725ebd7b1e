"""The decode executable's share of its byte roofline under sparse latent
attention: runs x the bytes a step must move (dense and absorbed matrices,
the indexers', the experts touched, the index keys of the live contexts,
the SELECTED latent rows, the head's rows) over peak bytes/s, against
traced seconds."""

from dsa_reduce import dsa_decode_roofline as read  # noqa: F401
