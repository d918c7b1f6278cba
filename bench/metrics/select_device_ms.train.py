"""Device time of the fused epoch program's ops under the
``agent_select`` scope (the agent's select), in ms per fleet-epoch."""
import scopes


def read(run):
    return scopes.layer_ms(run, "agent_select")
