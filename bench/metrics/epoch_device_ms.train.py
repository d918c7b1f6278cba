"""Device time of the fused epoch program (core/agent's ``_fleet_fn``,
the vmapped scan of ``api.make_epoch_step``) per fleet-epoch, in ms."""
import readers


def read(run):
    c = run.counters
    return readers.module_ms_per(run, "jit__fleet_fn(", c["jobs"] * c["epochs"])
