"""Percentiles, and JAX's own compile events counted by phase."""

from __future__ import annotations

import threading


def percentile(values, p: float) -> float:
    """Linear interpolation between closest ranks (NumPy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * p / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def cos_gap(got, want):
    """1 - cosine between rows of ``got`` (normalized here, float64) and
    the unit rows of ``want``."""
    import numpy as np

    got = np.asarray(got, np.float64)
    got = got / np.maximum(np.linalg.norm(got, axis=-1, keepdims=True), 1e-30)
    return 1.0 - np.sum(got * np.asarray(want, np.float64), axis=-1)


class CompileCounts:
    """Compile requests (every lowering handed to the backend), cache
    hits and cache writes, per phase. requests - hits = compilations."""

    _EVENTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_writes",
    }

    def __init__(self):
        self.phase = "setup"
        self.counts: dict[str, dict[str, int]] = {}
        self._lock = threading.Lock()

    def install(self) -> None:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _tick(self, field: str) -> None:
        with self._lock:
            row = self.counts.setdefault(
                self.phase, {"compile_requests": 0, "cache_hits": 0, "cache_writes": 0}
            )
            row[field] += 1

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self._tick("compile_requests")

    def _event(self, event: str, **_kw) -> None:
        field = self._EVENTS.get(event)
        if field is not None:
            self._tick(field)

    def requests(self, phase: str | None = None) -> int:
        return self.counts.get(phase or self.phase, {}).get("compile_requests", 0)
