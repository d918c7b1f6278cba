"""Runtime tracing-discipline guards (transfer guard, jit-cache-miss
sentinel, chunk-boundary NaN sweeps) and profiling marks: ``repro.*``
spans at the fleet runner's host boundaries, the fused epoch's layer
scopes and the DDPG sub-scopes inside them, and ``scope_tables()`` /
``subscope_tables()``, which map a compiled program's ops to those
scopes.  Static counterpart: ``tools/jaxguard``; rule catalog and
usage: docs/static_analysis.md."""
from repro.diagnostics.guards import (CompileCounter, GuardState,
                                      NonFiniteError, active, guards,
                                      maybe_check_finite)
from repro.diagnostics.spans import (LAYERS, SUBSCOPES, note_compile,
                                     scope_tables, span, subscope_tables)

__all__ = ["CompileCounter", "GuardState", "LAYERS", "NonFiniteError",
           "SUBSCOPES", "active", "guards", "maybe_check_finite",
           "note_compile", "scope_tables", "span", "subscope_tables"]
