"""Readers of the program's own marks in a traced training run.

``layer_ms`` splits the fused epoch program's device time by the layer
scopes of ``api.make_epoch_step`` (``env_step``, ``agent_select``,
``agent_update``), through the table from instruction name to scope that
``repro.diagnostics.scope_tables()`` builds from the compiled program.
``span_idle_ms`` puts the device's idle time down to the ``repro.*`` host
span open over it (``repro.fleet.prepare``, ``.dispatch``, ``.pull``),
splitting a gap where it crosses a span's edge.
A program without these marks gives None, and the harness leaves the
metric out.
"""
from __future__ import annotations

import bisect

PROGRAM = "jit__fleet_fn"
COVERED = 0.99        # share of the program's self time the table must name
SPAN_PREFIX = "repro."


def self_times(ops) -> list:
    """(op, self ns) of each ``(start_ns, end_ns, op)`` of one trace line:
    its duration less the time of the ops nested inside it, so an op that
    holds others (``%while``) counts only its own remainder."""
    out: list = []
    stack: list = []                       # [(end_ns, index in out)]
    for s, e, op in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            parent_end, i = stack[-1]
            out[i][1] -= min(e, parent_end) - s
        out.append([op, e - s])
        stack.append((e, len(out) - 1))
    return [(op, t) for op, t in out]


def program_self_ns(trace, table: dict) -> dict | None:
    """Self ns of the ``PROGRAM`` runs' ops in the window on the first
    device, by layer (None for ops outside every scope), or None when
    ``table`` names under ``COVERED`` of that time."""
    runs = trace.module_runs(PROGRAM + "(")
    if not runs:
        return None
    starts = [s for s, _ in runs]

    def inside(s, e):
        i = bisect.bisect_right(starts, s) - 1
        return i >= 0 and e <= runs[i][1]

    ops = [o for o in trace.devices[0].ops if inside(o[0], o[1])]
    by_layer: dict = {}
    total = named = 0
    for op, t in self_times(ops):
        total += t
        name = op.lstrip("%")
        if name in table:
            named += t
            by_layer[table[name]] = by_layer.get(table[name], 0) + t
    if total <= 0 or named < COVERED * total:
        return None
    return by_layer


def layer_ms(run, layer: str) -> float | None:
    """Device self time, in ms per fleet-epoch, of the fused epoch
    program's ops under the scope ``layer``."""
    try:
        from repro.diagnostics import scope_tables
    except ImportError:                    # a program without the scopes
        return None
    if run.trace is None:
        return None
    table = scope_tables().get(PROGRAM)
    by_layer = program_self_ns(run.trace, table) if table else None
    if by_layer is None:
        return None
    c = run.counters
    return by_layer.get(layer, 0) / 1e6 / (c["jobs"] * c["epochs"])


def owners(spans) -> tuple[list, list]:
    """The edges ``t_0 < ... < t_n`` of ``spans`` and, for each stretch
    ``[t_i, t_i+1)``, the name of the innermost span open over it (the
    one that starts last), or None where none is."""
    edges = sorted({t for s, e, _ in spans for t in (s, e)})
    owner = []
    for a, b in zip(edges, edges[1:]):
        open_ = [(s, -e, n) for s, e, n in spans if s <= a and b <= e]
        owner.append(max(open_)[2] if open_ else None)
    return edges, owner


def idle_by_span(trace) -> dict | None:
    """Idle ns of the first device by the innermost ``repro.*`` span of
    the host's main thread open over it (idle under none goes to None),
    or None when the trace holds no such span.  A gap that crosses a
    span's edge is split there, each stretch to its own span."""
    spans = [(s, e, n) for s, e, n in trace.host
             if n.startswith(SPAN_PREFIX)]
    if not spans:
        return None
    edges, owner = owners(spans)
    out: dict = {}
    for lo, hi in trace.idle_gaps():
        i = bisect.bisect_right(edges, lo)  # edges[i-1] <= lo < edges[i]
        t = lo
        while t < hi:
            end = min(hi, edges[i]) if i < len(edges) else hi
            name = owner[i - 1] if 0 < i < len(edges) else None
            out[name] = out.get(name, 0) + (end - t)
            t, i = end, i + 1
    return out


def span_idle_ms(run, span: str) -> float | None:
    """Device idle time, in ms per job, under the host span ``span``
    (``repro.fleet.pull``) and no ``repro.*`` span inside it."""
    idle = idle_by_span(run.trace) if run.trace is not None else None
    if idle is None:
        return None
    return idle.get(span, 0) / 1e6 / run.counters["jobs"]
