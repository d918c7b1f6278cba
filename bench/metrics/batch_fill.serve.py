"""Decisions served as a share of the slots dispatched, in %: how full
the plane's batches were in the traced window."""
import stats


def read(run):
    c = run.counters
    fill = stats.batch_fill(c["served"], c["dispatches"], c["n_slots"])
    return None if fill is None else 100.0 * fill
