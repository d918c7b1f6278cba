"""Device time of the fused epoch program's ops under the
``agent_update`` scope (observe, the replay update scan, tick), in ms per
fleet-epoch."""
import scopes


def read(run):
    return scopes.layer_ms(run, "agent_update")
