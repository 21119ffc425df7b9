"""Metric arithmetic shared by the metric readers. No jax."""

from __future__ import annotations

import math

INF = float("inf")


def percentile(values: list[float], q: float) -> float | None:
    """Nearest-rank percentile (q in 0..100) of ``values``; ``inf`` is a
    value like any other, so a failure counted as +inf moves the tail.
    None for an empty list."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def median(values: list[float]) -> float | None:
    return percentile(values, 50.0)


def whole_step_rate(groups: list[dict], chips: int) -> float | None:
    """Tokens of whole fetch groups finished in the window over the wall
    at the last group's end, per chip. ``groups``: dicts with ``t_end``
    (seconds from the window's start) and ``tokens``."""
    if not groups:
        return None
    return sum(g["tokens"] for g in groups) / groups[-1]["t_end"] / chips


def finite(x: float | None) -> float | None:
    """JSON has no infinity: a metric that is +inf is printed as 1e12 and
    the harness says so; NaN is no value."""
    if x is None or math.isnan(x):
        return None
    return 1e12 if math.isinf(x) else x
