"""Quantities the per-layer metric files read from a run.

Each file under ``bench/metrics/`` names one metric and calls one of
these; a reader returns None when the run holds nothing to read, and the
harness then leaves the metric out of the result."""
from __future__ import annotations


def module_ms_per(run, prefix: str, per: float) -> float | None:
    """Device milliseconds of the programs named ``prefix``... in the
    traced window, divided by ``per``."""
    runs = run.trace.module_runs(prefix) if run.trace else []
    if not runs or per <= 0:
        return None
    return sum(e - s for s, e in runs) / 1e6 / per


def mean_module_ms(run, prefix: str) -> float | None:
    """Mean device milliseconds of one run of the programs named
    ``prefix``... in the traced window."""
    runs = run.trace.module_runs(prefix) if run.trace else []
    return module_ms_per(run, prefix, len(runs)) if runs else None


def mean_gap_ms(run, prefix: str) -> float | None:
    """Mean time from the end of one run of the programs named
    ``prefix``... to the start of the next (device idle or busy with
    other programs)."""
    runs = run.trace.module_runs(prefix) if run.trace else []
    if len(runs) < 2:
        return None
    gaps = [b[0] - a[1] for a, b in zip(runs, runs[1:])]
    return sum(gaps) / len(gaps) / 1e6


def idle_pct(run) -> float | None:
    if run.trace is None or not run.trace.devices:
        return None
    return 100.0 * run.trace.idle_share()


def program_prefix(program) -> str:
    """The trace's name for a jitted function's program: ``jit_<name>(``."""
    return f"jit_{program.__name__}("
