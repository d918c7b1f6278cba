"""Device time of the fused epoch program's ops under the
``critic_target`` sub-scope (the update's target: target actor on s',
its K-NN beam, the target critic over the K candidates, the max), in ms
per fleet-epoch."""
import subscopes


def read(run):
    return subscopes.subscope_ms(run, "critic_target")
