"""What the host adds to one scan: mean over the traced stretch's
``index.search`` calls of the call's time less the ``knn.search.wait``
inside it (prepare, pad, lock, enqueue, the copy back, the hit loop)."""

from ring_reduce import knn_host_ms_per_search as read  # noqa: F401
