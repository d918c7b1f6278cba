"""Reader of the program's sub-scopes in a traced training run.

``subscope_ms`` splits the fused epoch program's device self time by the
DDPG sub-scopes (``knn_projection``, ``critic_target``) through the
table from instruction name to sub-scope path that
``repro.diagnostics.subscope_tables()`` builds from the compiled program,
as ``scopes.layer_ms`` does with the layer table.  The sub-scopes nest
(the target's K-NN beam is ``critic_target/knn_projection``), so a
sub-scope's time is that of every path that holds it.  A program without
the table gives None, and the harness leaves the metric out.
"""
from __future__ import annotations

import scopes


def subscope_ms(run, sub: str) -> float | None:
    """Device self time, in ms per fleet-epoch, of the fused epoch
    program's ops under the sub-scope ``sub``, or None where the table
    is missing or names under ``scopes.COVERED`` of the program's time."""
    try:
        from repro.diagnostics import subscope_tables
    except ImportError:                    # a program without sub-scopes
        return None
    if run.trace is None:
        return None
    table = subscope_tables().get(scopes.PROGRAM)
    by_path = scopes.program_self_ns(run.trace, table) if table else None
    if by_path is None:
        return None
    ns = sum(t for path, t in by_path.items()
             if path and sub in path.split("/"))
    c = run.counters
    return ns / 1e6 / (c["jobs"] * c["epochs"])
