"""Profiler spans at the fleet runner's host boundaries, layer scopes in
the fused epoch, and the map from a compiled program's instructions to
those scopes.

* :func:`span` opens a ``jax.profiler.TraceAnnotation`` named
  ``repro.<name>``.  A profiler session (``jax.profiler.trace``) holds it
  in the same ``.xplane.pb`` as the device events, on the same clock, so
  each stretch of device idle time can be put down to the span open
  around it.  Outside a session it is an inactive ``TraceMe``.
* :data:`LAYERS` are the ``jax.named_scope`` names that
  ``api.make_epoch_step`` puts around the env step, the agent's select
  and its update.  A scope is compile-time metadata: it lands in each
  HLO instruction's ``op_name`` and changes no operation.
* :data:`SUBSCOPES` are finer ``jax.named_scope`` names inside a layer:
  ``knn_projection`` around ``core.knn_projection.knn_actions_jax`` (the
  select's beam and the target's alike) and ``critic_target`` around
  ``core.ddpg._target_values`` (the target actor, its K-NN beam and the
  target critic over the candidates).  They nest, so an instruction's
  sub-scope is the path of those on its ``op_name``, outermost first
  (``critic_target/knn_projection``).
* :func:`note_compile` is called by the fleet runner after each program
  call.  When the program's trace cache grew (a compile) it records the
  program, its static arguments and the shapes of its arguments; on a
  cache hit it records nothing.
* :func:`scope_tables`, called after a profiled run, lowers each
  recorded program again from those shapes and reads the compiled text
  of the executable that ran (an in-memory cache hit), mapping every
  instruction name to the first layer scope on its ``op_name``.  A
  device trace names its ops by those instruction names (``%fusion.12``),
  so the table gives each op's layer.  A fusion that spans two scopes
  carries its root instruction's ``op_name``, and goes to that scope.
  :func:`subscope_tables` does the same with :func:`subscope_of`.

The executable a run used may come from the persistent compile cache.
Its ``op_name`` paths are its own only when the cache key holds the
metadata, which ``launch.compile_cache.enable_compile_cache`` turns on;
without it, an entry that an unscoped lowering of the same program
filled would carry no scopes.
"""
from __future__ import annotations

import re

import jax

from repro.diagnostics.guards import _cache_size

ENV_STEP, AGENT_SELECT, AGENT_UPDATE = LAYERS = (
    "env_step", "agent_select", "agent_update")
KNN_PROJECTION, CRITIC_TARGET = SUBSCOPES = ("knn_projection",
                                             "critic_target")

# (program, statics, arg treedef, arg specs) -> (module name,
# {instruction name: op_name or None}) of that compile, or None until a
# table is first asked for
_COMPILED: dict = {}

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A profiler span named ``repro.<name>``."""
    return jax.profiler.TraceAnnotation("repro." + name)


def _spec(x) -> jax.ShapeDtypeStruct:
    """The argument as jit saw it: an uncommitted array leaves its
    placement to jit, and a committed one (or a spec) pins its sharding.
    Lowered from these, the program is the one that ran, and its compile
    finds that executable in memory."""
    aval = jax.typeof(x)
    pinned = getattr(x, "_committed", isinstance(x, jax.ShapeDtypeStruct))
    return jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                weak_type=aval.weak_type,
                                sharding=x.sharding if pinned else None)


def note_compile(program, cache_before: int, args: tuple,
                 statics: dict) -> None:
    """Record ``program`` with its ``statics`` and the shapes of ``args``
    when its trace cache grew past ``cache_before``."""
    if _cache_size(program) <= cache_before:
        return
    leaves, tree = jax.tree.flatten(args)
    key = (program, tuple(sorted(statics.items())), tree,
           tuple(_spec(x) for x in leaves))
    _COMPILED.setdefault(key, None)


def layer_of(op_name: str) -> str | None:
    """The first of :data:`LAYERS` on an ``op_name`` path such as
    ``jit(f)/vmap()/while/body/agent_update/transpose(jvp(...))/dot``."""
    for part in re.split(r"[/()]", op_name):
        if part in LAYERS:
            return part
    return None


def subscope_of(op_name: str) -> str | None:
    """The :data:`SUBSCOPES` on an ``op_name`` path, outermost first and
    joined by ``/`` (``critic_target/knn_projection``), or None."""
    found = [p for p in re.split(r"[/()]", op_name) if p in SUBSCOPES]
    return "/".join(dict.fromkeys(found)) or None


def _op_names(text: str) -> tuple[str, dict]:
    """(module name, {instruction name: op_name or None}) of an HLO text."""
    module, ops = "", {}
    for line in text.splitlines():
        if not module and (m := _MODULE.match(line)):
            module = m.group(1)
            continue
        if m := _INSTRUCTION.match(line):
            op = _OP_NAME.search(line)
            ops[m.group(1)] = op.group(1) if op else None
    return module, ops


def parse_hlo(text: str) -> tuple[str, dict]:
    """(module name, {instruction name: layer or None}) of an HLO text."""
    module, ops = _op_names(text)
    return module, {name: layer_of(op) if op else None
                    for name, op in ops.items()}


def scope_tables(scope_of=layer_of) -> dict[str, dict]:
    """``{program trace name: {instruction name: layer or None}}`` over
    every program :func:`note_compile` recorded (with ``scope_of``
    another classification of the ``op_name``s).  Compiles of one program
    under the same trace name merge; an instruction name whose scope
    differs between them is left out, so a reader cannot misplace it."""
    for key, ops in _COMPILED.items():
        if ops is None:
            program, statics, tree, specs = key
            lowered = program.lower(*jax.tree.unflatten(tree, specs),
                                    **dict(statics))
            _COMPILED[key] = _op_names(lowered.compile().as_text())
    merged: dict[str, dict] = {}
    clash: dict[str, set] = {}
    for module, ops in _COMPILED.values():
        out = merged.setdefault(module, {})
        bad = clash.setdefault(module, set())
        for name, op in ops.items():
            layer = scope_of(op) if op else None
            if out.setdefault(name, layer) != layer:
                bad.add(name)
    for module, bad in clash.items():
        for name in bad:
            del merged[module][name]
    return merged


def subscope_tables() -> dict[str, dict]:
    """``{program trace name: {instruction name: sub-scope path or
    None}}``, built as :func:`scope_tables` builds the layer table."""
    return scope_tables(subscope_of)
