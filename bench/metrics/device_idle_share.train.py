"""Share of the traced training window in which no operation ran on the
device, in %, averaged over the chips used."""
import readers


def read(run):
    return readers.idle_pct(run)
