"""The host's share inside the encoder: seconds in ``encoder.encode``
less ``encoder.tokenize`` and ``encoder.wait`` (padding, the copies in
and out, the dispatch), over the traced stretch."""

from ring_reduce import encoder_host_share as read  # noqa: F401
