"""Device idle time under the host span ``repro.fleet.pull``
(``fleet_host`` bringing the traces home), in ms per job."""
import scopes


def read(run):
    return scopes.span_idle_ms(run, "repro.fleet.pull")
