"""Device time of the fused epoch program's ops under the ``env_step``
scope (the simulator's state vector and step), in ms per fleet-epoch."""
import scopes


def read(run):
    return scopes.layer_ms(run, "env_step")
