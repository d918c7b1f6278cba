"""Device time of the fused epoch program's ops under the
``knn_projection`` sub-scope (the K nearest assignments of the select's
proto-action and of every target sample's), in ms per fleet-epoch."""
import subscopes


def read(run):
    return subscopes.subscope_ms(run, "knn_projection")
