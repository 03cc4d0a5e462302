"""Statistics the metric readers share."""
from __future__ import annotations

import math


def percentile(values: list[float], q: float) -> float | None:
    """Nearest-rank ``q``-th percentile of every value (infinities
    included: a missing frame is slower than every other)."""
    if not values:
        return None
    xs = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def engine_phase_ms(run, *phases: str) -> float | None:
    """Sum of the means of the engine report's phases, in ms."""
    rep = run.engine_report
    if rep is None:
        return None
    got = rep["measured"]["phases"]
    if any(p not in got for p in phases):
        return None
    return sum(got[p]["mean_ms"] for p in phases)
