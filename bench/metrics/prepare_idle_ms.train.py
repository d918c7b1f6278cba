"""Device idle time under the host span ``repro.fleet.prepare``
(``prepare_fleet``: key split, env reset, placement), in ms per job."""
import scopes


def read(run):
    return scopes.span_idle_ms(run, "repro.fleet.prepare")
