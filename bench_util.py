"""Measurement policy of scripts/bench_relational.py: median-of-runs
selection with dispersion flagging."""

from __future__ import annotations

import statistics

DISPERSION_FLAG = 0.2


def dispersion(values: list[float]) -> float:
    med = statistics.median(values)
    return round((max(values) - min(values)) / med, 3) if med else 0.0


def median_index(rates: list[float]) -> int:
    """Index of the run whose rate is the median."""
    return rates.index(sorted(rates)[len(rates) // 2])


def median_of(runs: list[dict], rates: list[float]) -> dict:
    """The run whose rate is the median, annotated with the spread."""
    out = dict(runs[median_index(rates)])
    out["runs"] = [round(r, 1) for r in rates]
    out["dispersion"] = dispersion(rates)
    out["unsteady"] = dispersion(rates) > DISPERSION_FLAG
    return out
