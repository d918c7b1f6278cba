"""Metric arithmetic shared by the jobs: percentiles, rates, lateness.

``nearest_rank_percentile`` is a copy of the program's
``serve.control.nearest_rank_percentile``; the benchmark keeps its own so
that a change to the program cannot change how it is measured."""
from __future__ import annotations

import math


def nearest_rank_percentile(samples, q: float) -> float:
    """Deterministic nearest-rank percentile (no interpolation): the
    smallest sample with at least q% of the samples at or below it.
    ``math.inf`` samples (requests never answered) sort last."""
    if not len(samples):
        raise ValueError("percentile of an empty sample")
    xs = sorted(float(x) for x in samples)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def due_latencies_ms(due_s, answered_s) -> list[float]:
    """Latency of every request due in the window, from the time it was
    due (not the time it was submitted) to the return of the step that
    answered it; ``None`` (never answered) counts as ``math.inf``."""
    return [math.inf if a is None else (a - d) * 1e3
            for d, a in zip(due_s, answered_s)]


def rate_per_s(units: float, wall_s: float, chips: int) -> float:
    """Work done per second per chip over the whole window."""
    if wall_s <= 0.0:
        raise ValueError(f"window of {wall_s} s")
    return units / wall_s / chips


def batch_fill(served: int, dispatches: int, slots: int) -> float | None:
    """Decisions served as a share of the slots dispatched, or None when
    nothing was dispatched."""
    if dispatches == 0:
        return None
    return served / (dispatches * slots)
