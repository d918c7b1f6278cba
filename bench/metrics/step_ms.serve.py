"""Mean wall time, in ms, of the ``ControlPlane.step`` calls that served
at least one request (admission, batch assembly, dispatch, pull, retire),
timed by the harness around each call in the traced window."""


def read(run):
    steps = run.counters["step_s"]
    return 1e3 * sum(steps) / len(steps) if steps else None
