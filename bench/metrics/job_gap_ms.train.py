"""Mean gap, in ms, between the end of one job's fleet program and the
start of the next: ``run_online_fleet``'s host boundaries (prepare_fleet,
trace pulls, finite checks) as the device sees them."""
import readers


def read(run):
    return readers.mean_gap_ms(run, "jit__fleet_fn(")
