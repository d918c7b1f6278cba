"""Runtime tracing-discipline guards (transfer guard, jit-cache-miss
sentinel, chunk-boundary NaN sweeps) and profiling marks: ``repro.*``
spans at the fleet runner's host boundaries, the fused epoch's layer
scopes, and ``scope_tables()``, which maps a compiled program's ops to
those scopes.  Static counterpart: ``tools/jaxguard``; rule catalog and
usage: docs/static_analysis.md."""
from repro.diagnostics.guards import (CompileCounter, GuardState,
                                      NonFiniteError, active, guards,
                                      maybe_check_finite)
from repro.diagnostics.spans import LAYERS, note_compile, scope_tables, span

__all__ = ["CompileCounter", "GuardState", "LAYERS", "NonFiniteError",
           "active", "guards", "maybe_check_finite", "note_compile",
           "scope_tables", "span"]
