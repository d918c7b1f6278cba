"""Share of the traced serving window in which no operation ran on the
device, in %."""
import readers


def read(run):
    return readers.idle_pct(run)
