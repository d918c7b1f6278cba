"""Device idle time under the host span ``repro.fleet.dispatch`` (the
fleet program's call in ``run_fleet_chunk``), in ms per job."""
import scopes


def read(run):
    return scopes.span_idle_ms(run, "repro.fleet.dispatch")
