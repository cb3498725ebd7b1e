"""How long a question waits in the gateway before its window's commit
starts: median, over the requests admitted in the traced stretch, of the
ring's ``gateway.queue`` (admission to window close) plus its window's
``gateway.pickup`` (close to the dispatch worker holding the lock)."""

from ring_reduce import gateway_wait_p50_ms as read  # noqa: F401
