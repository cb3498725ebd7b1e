"""Bytes over the host-device boundary per document made searchable:
h2d + d2h bytes of every ring span of the commits that ran whole inside
the traced stretch (ids and lengths in and embeddings out at the
encoder, the same embeddings back in at ``knn.write``), over the
documents those commits encoded."""

from ring_reduce import transfer_bytes_per_doc as read  # noqa: F401
