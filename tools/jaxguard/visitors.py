"""AST analysis implementing the jaxguard rules (JG001–JG007).

One :class:`Analyzer` per file, two phases:

* a module scan that resolves import aliases (``jnp`` → ``jax.numpy``),
  registers module-level jitted bindings (``_prog = jax.jit(fn, ...)``),
  their ``donate_argnums``, and the set of functions whose bodies are
  traced (jit-decorated, jit-wrapped, or passed to ``lax.scan``/``vmap``
  and friends, plus everything lexically nested inside them);
* a rule walk that flags violations, with a per-function linear dataflow
  pass for the order-sensitive rules (JG001 key reuse, JG006 donated
  reads).

The dataflow is deliberately line-ordered and intra-procedural: it does
not follow aliases, attributes, or control-flow joins.  That keeps false
positives rare enough that ``python -m tools.jaxguard src/`` can be a
blocking CI job; the escape hatch for deliberate patterns is a
``# jaxguard: disable=RULE`` comment (suppress.py).  Nested function
bodies are analyzed as their own scopes, not inlined into the enclosing
function's dataflow.
"""
from __future__ import annotations

import ast
import dataclasses

from tools.jaxguard.report import Finding
from tools.jaxguard.suppress import Suppressions

# canonical names --------------------------------------------------------
_JIT = {"jax.jit", "jax.pmap"}
_VMAP = {"jax.vmap"}
_PARTIAL = "functools.partial"
_CACHE_DECOS = {"functools.lru_cache", "functools.cache"}
_SPLIT = "jax.random.split"
# entry points whose function arguments get traced
_TRACE_ENTRY = _JIT | _VMAP | {
    "jax.lax.scan", "jax.lax.map", "jax.lax.while_loop", "jax.lax.cond",
    "jax.lax.fori_loop", "jax.lax.associative_scan", "jax.grad",
    "jax.value_and_grad", "jax.checkpoint", "jax.remat", "jax.linearize",
    "jax.shard_map", "jax.experimental.shard_map.shard_map",
}
# jnp constructors whose all-literal calls are per-iteration h2d transfers
_JNP_CONSTRUCTORS = {
    "array", "asarray", "zeros", "ones", "full", "arange", "eye",
    "float32", "float64", "int32", "int64", "bfloat16", "float16",
}
# callables that are safe as function defaults
_DEFAULT_WHITELIST = {
    "field", "dataclasses.field", "frozenset", "tuple", "property",
    "functools.partial", "partial", "MappingProxyType",
}
_HOST_SYNC_BUILTINS = {"float", "int", "bool", "complex"}


def _dotted(node: ast.AST) -> str | None:
    """Raw dotted name of a Name/Attribute chain (``jnp.asarray``)."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


@dataclasses.dataclass
class _JitSite:
    """One jax.jit(...) call site with its resolved target + keywords."""

    call: ast.Call
    target: ast.FunctionDef | None
    static_argnames: list[str] | None   # None = present but unresolvable
    static_argnums: list[int] | None
    donate_argnums: list[int] | None
    has_static_names_kw: bool
    has_static_nums_kw: bool


class Analyzer:
    """Per-file rule analysis; ``run()`` returns unsuppressed findings."""

    def __init__(self, path: str, source: str, select: set[str] | None = None):
        self.path = path
        self.source = source
        self.select = select
        self.findings: list[Finding] = []
        self.aliases: dict[str, str] = {}
        self.module_consts: dict[str, ast.expr] = {}
        self.donated: dict[str, list[int]] = {}
        self.cache_exempt: set[ast.AST] = set()
        self.traced: set[ast.AST] = set()
        self._all_defs: list[tuple[tuple[ast.AST, ...], ast.AST]] = []

    # -- name resolution -------------------------------------------------
    def qual(self, node: ast.AST) -> str | None:
        """Canonical dotted name with the head import-alias resolved."""
        raw = _dotted(node)
        if raw is None:
            return None
        head, _, rest = raw.partition(".")
        head = self.aliases.get(head, head)
        return f"{head}.{rest}" if rest else head

    # -- entry point -------------------------------------------------------
    def run(self) -> list[Finding]:
        try:
            tree = ast.parse(self.source, filename=self.path)
        except SyntaxError as e:
            # a file that does not parse cannot be vetted — surface it
            self._emit("JG002", e.lineno or 1, 0,
                       f"file does not parse: {e.msg}")
            return self._filtered()
        self._scan_module(tree)
        self._walk(tree, func_stack=(), loop_stack=(), class_stack=())
        return self._filtered()

    def _filtered(self) -> list[Finding]:
        sup = Suppressions(self.source)
        out = [f for f in self.findings
               if not sup.is_suppressed(f.line, f.code)]
        if self.select is not None:
            out = [f for f in out if f.code in self.select]
        return sorted(out)

    def _emit(self, code: str, line: int, col: int, msg: str) -> None:
        self.findings.append(Finding(path=self.path, line=line, col=col,
                                     code=code, message=msg))

    # =====================================================================
    # phase A: module scan
    # =====================================================================
    def _scan_module(self, tree: ast.Module) -> None:
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.aliases[a.asname or a.name.split(".")[0]] = \
                        a.name if a.asname else a.name.split(".")[0]
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.aliases[a.asname or a.name] = \
                        f"{node.module}.{a.name}"
        # module-level constant tuples (for static_argnames=_STATICS)
        for stmt in tree.body:
            if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)):
                self.module_consts[stmt.targets[0].id] = stmt.value
        # defs in lexical order with their enclosing-scope stack
        def collect(node, stack):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.Lambda)):
                    self._all_defs.append((stack, child))
                    collect(child, stack + (child,))
                else:
                    collect(child, stack)
        collect(tree, ())

        # decorated defs: jit/cache exemptions, donation registry
        for _, d in self._all_defs:
            if isinstance(d, ast.Lambda):
                continue
            for deco in d.decorator_list:
                site = self._parse_jit_call(deco, target=d)
                if site is not None:
                    self.traced.add(d)
                    if site.donate_argnums:
                        self.donated[d.name] = site.donate_argnums
                if self._is_cache_deco(deco):
                    self.cache_exempt.add(d)

        # module-level `name = jax.jit(fn, ...)` bindings
        defs_by_name = {d.name: d for _, d in self._all_defs
                        if isinstance(d, (ast.FunctionDef,
                                          ast.AsyncFunctionDef))}
        for stmt in tree.body:
            if not (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)
                    and isinstance(stmt.value, ast.Call)):
                continue
            site = self._parse_jit_call(stmt.value)
            if site is None:
                continue
            args = stmt.value.args
            if args and isinstance(args[0], ast.Name):
                site.target = defs_by_name.get(args[0].id)
                if site.target is not None:
                    self.traced.add(site.target)
            if site.donate_argnums:
                self.donated[stmt.targets[0].id] = site.donate_argnums

        # functions handed to tracing entry points anywhere in the file
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and self.qual(node.func) in _TRACE_ENTRY):
                continue
            for arg in node.args:
                if isinstance(arg, ast.Lambda):
                    self.traced.add(arg)
                elif isinstance(arg, ast.Name):
                    d = self._lookup_def(arg.id, node)
                    if d is not None:
                        self.traced.add(d)
        # closure: everything nested inside a traced def is traced
        changed = True
        while changed:
            changed = False
            for stack, d in self._all_defs:
                if d not in self.traced and any(s in self.traced
                                                for s in stack):
                    self.traced.add(d)
                    changed = True

    def _lookup_def(self, name: str, at: ast.AST):
        """Innermost FunctionDef named ``name`` (lexical heuristic)."""
        best = None
        for _, d in self._all_defs:
            if isinstance(d, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and d.name == name:
                best = d
        return best

    def _is_cache_deco(self, deco: ast.AST) -> bool:
        q = self.qual(deco.func if isinstance(deco, ast.Call) else deco)
        return q in _CACHE_DECOS

    # -- jit call parsing --------------------------------------------------
    def _parse_jit_call(self, node: ast.AST,
                        target: ast.FunctionDef | None = None):
        """A _JitSite if ``node`` is jax.jit(...)/partial(jax.jit, ...) (or
        a bare ``@jax.jit`` decorator when ``target`` is given)."""
        if target is not None and not isinstance(node, ast.Call):
            return (_JitSite(call=None, target=target, static_argnames=[],
                             static_argnums=[], donate_argnums=[],
                             has_static_names_kw=False,
                             has_static_nums_kw=False)
                    if self.qual(node) in _JIT else None)
        if not isinstance(node, ast.Call):
            return None
        q = self.qual(node.func)
        call = node
        if q == _PARTIAL:
            if not (node.args and self.qual(node.args[0]) in _JIT):
                return None
        elif q not in _JIT:
            return None
        names = nums = donate = []
        has_names = has_nums = False
        names, has_names = self._kw_strings(call, "static_argnames")
        nums, has_nums = self._kw_ints(call, "static_argnums")
        donate, _ = self._kw_ints(call, "donate_argnums")
        return _JitSite(call=call, target=target, static_argnames=names,
                        static_argnums=nums, donate_argnums=donate,
                        has_static_names_kw=has_names,
                        has_static_nums_kw=has_nums)

    def _const_value(self, node: ast.expr, depth: int = 0):
        """Fold literals, module-level constant Names, and tuple `+`."""
        if depth > 4 or node is None:
            return None
        if isinstance(node, ast.Constant):
            return node.value
        if isinstance(node, (ast.Tuple, ast.List)):
            vals = [self._const_value(e, depth + 1) for e in node.elts]
            return None if any(v is None for v in vals) else tuple(vals)
        if isinstance(node, ast.Name) and node.id in self.module_consts:
            return self._const_value(self.module_consts[node.id], depth + 1)
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
            left = self._const_value(node.left, depth + 1)
            right = self._const_value(node.right, depth + 1)
            if isinstance(left, tuple) and isinstance(right, tuple):
                return left + right
        return None

    def _kw_strings(self, call: ast.Call, kw: str):
        for k in call.keywords:
            if k.arg == kw:
                v = self._const_value(k.value)
                if isinstance(v, str):
                    return [v], True
                if isinstance(v, tuple) and all(isinstance(x, str)
                                                for x in v):
                    return list(v), True
                return None, True
        return [], False

    def _kw_ints(self, call: ast.Call, kw: str):
        for k in call.keywords:
            if k.arg == kw:
                v = self._const_value(k.value)
                if isinstance(v, int) and not isinstance(v, bool):
                    return [v], True
                if isinstance(v, tuple) and all(
                        isinstance(x, int) and not isinstance(x, bool)
                        for x in v):
                    return list(v), True
                return None, True
        return [], False

    # =====================================================================
    # phase B: rule walk
    # =====================================================================
    def _walk(self, node, func_stack, loop_stack, class_stack) -> None:
        self._walk_nodes(ast.iter_child_nodes(node), func_stack, loop_stack,
                         class_stack)

    def _walk_nodes(self, children, func_stack, loop_stack,
                    class_stack) -> None:
        for child in children:
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._check_defaults(child, class_stack)
                for deco in child.decorator_list:
                    self._check_jit_site(deco, func_stack, loop_stack,
                                         decorator_target=child)
                self._function_dataflow(child)
                # recurse into the BODY only: decorators and defaults were
                # handled above and must not re-trip the in-function rules
                self._walk_nodes(child.body, func_stack + (child,), (),
                                 class_stack)
            elif isinstance(child, ast.Lambda):
                self._walk(child, func_stack + (child,), loop_stack,
                           class_stack)
            elif isinstance(child, ast.ClassDef):
                self._check_dataclass_fields(child)
                self._walk(child, func_stack, loop_stack,
                           class_stack + (child,))
            elif isinstance(child, (ast.For, ast.AsyncFor, ast.While)):
                self._walk(child, func_stack, loop_stack + (child,),
                           class_stack)
            else:
                if isinstance(child, ast.Call):
                    self._check_jit_site(child, func_stack, loop_stack)
                    self._check_jnp_constant(child, func_stack, loop_stack)
                    self._check_host_sync(child, func_stack)
                self._walk(child, func_stack, loop_stack, class_stack)

    # -- JG002 + JG003 ----------------------------------------------------
    def _check_jit_site(self, node, func_stack, loop_stack,
                        decorator_target=None) -> None:
        # jax.vmap in a loop (vmap has no cache at all) — checked before
        # the jit parse, which returns None for vmap calls
        if (isinstance(node, ast.Call) and self.qual(node.func) in _VMAP
                and loop_stack and decorator_target is None
                and not any(f in self.traced for f in func_stack)):
            self._emit("JG002", node.lineno, node.col_offset,
                       "jax.vmap constructed inside a loop — vmap has no "
                       "cache; each iteration re-traces the mapped function")
        site = self._parse_jit_call(node, target=decorator_target)
        if site is None:
            return
        if site.target is None and site.call is not None \
                and self.qual(site.call.func) != _PARTIAL \
                and site.call.args and isinstance(site.call.args[0], ast.Name):
            site.target = self._lookup_def(site.call.args[0].id, node)
        line, col = node.lineno, node.col_offset
        in_function = any(isinstance(f, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))
                          for f in func_stack)
        exempt = any(f in self.cache_exempt for f in func_stack)
        if decorator_target is None and site.call is not None:
            kind = self.qual(site.call.func)
            kind = "functools.partial(jax.jit, ...)" if kind == _PARTIAL \
                else kind
            if loop_stack:
                self._emit("JG002", line, col,
                           f"{kind} constructed inside a loop — a fresh "
                           f"wrapper per iteration re-traces and "
                           f"re-compiles every time; hoist it out")
            elif in_function and not exempt:
                self._emit("JG002", line, col,
                           f"{kind} constructed inside a function body — "
                           f"each call builds a fresh wrapper with an "
                           f"empty trace cache (per-call re-jit); hoist "
                           f"to module scope, a decorator, or an "
                           f"lru_cache'd builder")
        elif decorator_target is not None and in_function and not exempt:
            self._emit("JG002", line, col,
                       f"jitted function {decorator_target.name!r} defined "
                       f"inside a function body — the decorator runs per "
                       f"enclosing call, so its trace cache never survives; "
                       f"hoist to module scope")
        self._check_statics(site)

    def _check_statics(self, site: _JitSite) -> None:
        if site.call is None:
            return
        line, col = site.call.lineno, site.call.col_offset
        if site.has_static_names_kw and site.static_argnames is None:
            return          # dynamic expression we could not fold — skip
        if site.target is None:
            return          # target signature unknown — nothing to check
        a = site.target.args
        params = ([p.arg for p in a.posonlyargs] + [p.arg for p in a.args]
                  + [p.arg for p in a.kwonlyargs])
        n_positional = len(a.posonlyargs) + len(a.args)
        for name in site.static_argnames or []:
            if name not in params:
                self._emit("JG003", line, col,
                           f"static_argnames names {name!r} but "
                           f"{site.target.name!r} has no such parameter "
                           f"(has: {', '.join(params)}) — the intended "
                           f"static is silently ignored")
        for num in site.static_argnums or []:
            if num >= n_positional or num < -n_positional:
                self._emit("JG003", line, col,
                           f"static_argnums {num} is out of range for "
                           f"{site.target.name!r} ({n_positional} "
                           f"positional parameters)")
        # unhashable default on a parameter declared static
        static_set = set(site.static_argnames or [])
        for num in site.static_argnums or []:
            if 0 <= num < n_positional:
                static_set.add(params[num])
        pos_params = a.posonlyargs + a.args
        defaults = a.defaults
        offset = len(pos_params) - len(defaults)
        pairs = [(p.arg, d) for p, d in zip(pos_params[offset:], defaults)]
        pairs += [(p.arg, d) for p, d in zip(a.kwonlyargs, a.kw_defaults)
                  if d is not None]
        for pname, d in pairs:
            if pname in static_set and isinstance(
                    d, (ast.List, ast.Dict, ast.Set)):
                self._emit("JG003", d.lineno, d.col_offset,
                           f"parameter {pname!r} is declared static but "
                           f"defaults to an unhashable "
                           f"{type(d).__name__.lower()} literal — jit "
                           f"will fail to hash it at call time")

    # -- JG004 ------------------------------------------------------------
    def _check_jnp_constant(self, node: ast.Call, func_stack,
                            loop_stack) -> None:
        if not loop_stack or not node.args:
            return
        if any(f in self.traced for f in func_stack):
            return                      # trace-time loop: compiles once
        q = self.qual(node.func)
        if not (q and q.startswith("jax.numpy.")
                and q.rsplit(".", 1)[1] in _JNP_CONSTRUCTORS):
            return

        def literal(e) -> bool:
            if isinstance(e, ast.Constant):
                return True
            if isinstance(e, (ast.Tuple, ast.List)):
                return all(literal(x) for x in e.elts)
            if isinstance(e, ast.UnaryOp):
                return literal(e.operand)
            return False

        if all(literal(a) for a in node.args):
            self._emit("JG004", node.lineno, node.col_offset,
                       f"{_dotted(node.func)}(...) built from Python "
                       f"literals inside a loop — one host-to-device "
                       f"transfer per iteration for a constant; hoist it "
                       f"above the loop")

    # -- JG005 ------------------------------------------------------------
    def _check_defaults(self, fn, class_stack) -> None:
        a = fn.args
        pos_params = a.posonlyargs + a.args
        offset = len(pos_params) - len(a.defaults)
        pairs = list(zip(pos_params[offset:], a.defaults))
        pairs += [(p, d) for p, d in zip(a.kwonlyargs, a.kw_defaults)
                  if d is not None]
        for p, d in pairs:
            msg = self._mutable_default_msg(d)
            if msg:
                self._emit("JG005", d.lineno, d.col_offset,
                           f"parameter {p.arg!r} of {fn.name!r}: {msg}")

    def _mutable_default_msg(self, d: ast.expr) -> str | None:
        if isinstance(d, (ast.List, ast.Dict, ast.Set)):
            return (f"mutable {type(d).__name__.lower()} literal default — "
                    f"evaluated once at def time and shared across every "
                    f"call; use None and construct in the body")
        if isinstance(d, ast.Call):
            raw = _dotted(d.func)
            if raw is None or raw in _DEFAULT_WHITELIST \
                    or raw.rsplit(".", 1)[-1] in _DEFAULT_WHITELIST:
                return None
            last = raw.rsplit(".", 1)[-1]
            if last in {"list", "dict", "set"} or (last and
                                                   last[0].isupper()):
                return (f"default constructed by calling {raw}() in the "
                        f"signature — the single instance is evaluated "
                        f"once at def time and shared across every call; "
                        f"use None and construct in the body")
        return None

    def _check_dataclass_fields(self, cls: ast.ClassDef) -> None:
        is_dc = any(
            self.qual(d.func if isinstance(d, ast.Call) else d)
            in {"dataclasses.dataclass", "dataclass",
                "flax.struct.dataclass", "chex.dataclass"}
            for d in cls.decorator_list)
        if not is_dc:
            return
        for stmt in cls.body:
            if not (isinstance(stmt, ast.AnnAssign)
                    and stmt.value is not None):
                continue
            v = stmt.value
            bad = None
            if isinstance(v, (ast.List, ast.Dict, ast.Set)):
                bad = f"a mutable {type(v).__name__.lower()} literal"
            elif isinstance(v, ast.Call):
                q = self.qual(v.func) or ""
                raw = _dotted(v.func) or ""
                if raw.rsplit(".", 1)[-1] in {"list", "dict", "set"} or \
                        q.startswith(("numpy.", "jax.numpy.")):
                    bad = f"an array/collection built by {raw}()"
            if bad:
                name = stmt.target.id if isinstance(stmt.target, ast.Name) \
                    else "?"
                self._emit("JG005", v.lineno, v.col_offset,
                           f"pytree dataclass field {name!r} defaults to "
                           f"{bad} — one shared instance across every "
                           f"dataclass instance; use "
                           f"dataclasses.field(default_factory=...)")

    # -- JG007 ------------------------------------------------------------
    def _check_host_sync(self, node: ast.Call, func_stack) -> None:
        if not any(f in self.traced for f in func_stack):
            return
        line, col = node.lineno, node.col_offset

        def is_dynamic(e) -> bool:
            # attribute access is overwhelmingly static-config access
            # (cfg.lr, self.n) — skip it to keep the rule quiet
            return isinstance(e, (ast.Name, ast.Subscript, ast.Call,
                                  ast.BinOp))

        q = self.qual(node.func)
        raw = _dotted(node.func)
        if q in _HOST_SYNC_BUILTINS and len(node.args) == 1 \
                and is_dynamic(node.args[0]):
            self._emit("JG007", line, col,
                       f"{q}(...) on a (possibly traced) value inside a "
                       f"jitted code path — concretizes the tracer: "
                       f"either a trace-time error or a silent "
                       f"device-to-host sync")
        elif q and q.startswith("numpy.") and node.args \
                and is_dynamic(node.args[0]) \
                and q.rsplit(".", 1)[1] in {"asarray", "array", "float32",
                                            "float64", "int32", "int64"}:
            self._emit("JG007", line, col,
                       f"{raw}(...) inside a jitted code path pulls the "
                       f"value to host numpy — use jnp (stays traced) or "
                       f"move the conversion outside the jitted function")
        elif isinstance(node.func, ast.Attribute) \
                and node.func.attr == "item" and not node.args:
            self._emit("JG007", line, col,
                       ".item() inside a jitted code path — a forced "
                       "device-to-host sync on a traced value")

    # =====================================================================
    # per-function linear dataflow: JG001 + JG006
    # =====================================================================
    def _function_dataflow(self, fn) -> None:
        own = self._own_nodes(fn)
        stores = [(n.lineno, n.id) for n in own
                  if isinstance(n, ast.Name)
                  and isinstance(n.ctx, (ast.Store, ast.Del))]
        loads = [(n.lineno, n.col_offset, n.id) for n in own
                 if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)]
        loops = [n for n in own
                 if isinstance(n, (ast.For, ast.AsyncFor, ast.While))]
        # return/raise lines: a terminator between consumption and use
        # usually means the two sit in mutually-exclusive branches, which
        # this linear pass cannot tell apart — stay quiet there
        exits = [(n.lineno, n.end_lineno or n.lineno) for n in own
                 if isinstance(n, (ast.Return, ast.Raise))]

        def stored_between(name, lo, hi) -> bool:
            return any(nm == name and lo < ln <= hi for ln, nm in stores)

        def flag_uses_after(name, line, code, msg_fn) -> None:
            flagged = 0
            for ln, col, nm in sorted(loads):
                if nm != name or ln <= line:
                    continue
                if stored_between(name, line, ln):
                    break
                if any(line < ex and ex_end < ln for ex, ex_end in exits):
                    break
                self._emit(code, ln, col, msg_fn(ln))
                flagged += 1
                if flagged >= 2:        # cap the noise per consumption
                    break

        for stmt in own:
            if not isinstance(stmt, ast.Call):
                continue
            # JG001: jax.random.split(key) consumption
            if self.qual(stmt.func) == _SPLIT and stmt.args \
                    and isinstance(stmt.args[0], ast.Name):
                key = stmt.args[0].id
                targets = self._stmt_targets(stmt, fn)
                if key in targets:
                    continue            # `key, sub = split(key)` rebinding
                flag_uses_after(
                    key, stmt.lineno, "JG001",
                    lambda ln, k=key, sl=stmt.lineno: (
                        f"PRNG key {k!r} used again after "
                        f"jax.random.split({k}, ...) consumed it at line "
                        f"{sl} — derived streams are correlated; rebind "
                        f"(`{k}, sub = jax.random.split({k})`) or fold_in"))
                enclosing = [lp for lp in loops
                             if lp.lineno <= stmt.lineno
                             <= (lp.end_lineno or lp.lineno)
                             # `for k in split(key, n):` splits once per
                             # *enclosing* pass, not per iteration — the
                             # header is not inside the loop body
                             and not any(n is stmt for n in ast.walk(
                                 lp.iter if isinstance(
                                     lp, (ast.For, ast.AsyncFor))
                                 else lp.test))]
                if enclosing:
                    loop = enclosing[-1]
                    lo, hi = loop.lineno, loop.end_lineno or loop.lineno
                    if not any(nm == key and lo <= ln <= hi
                               for ln, nm in stores):
                        self._emit(
                            "JG001", stmt.lineno, stmt.col_offset,
                            f"jax.random.split({key!r}, ...) inside a loop "
                            f"without rebinding {key!r} — every iteration "
                            f"derives the SAME streams; rebind the key "
                            f"each pass or split once outside")
            # JG006: donated-buffer reads after a donating call
            callee = _dotted(stmt.func)
            if callee in self.donated:
                targets = self._stmt_targets(stmt, fn)
                for idx in self.donated[callee]:
                    if idx >= len(stmt.args):
                        continue
                    arg = stmt.args[idx]
                    if not isinstance(arg, ast.Name) or arg.id in targets:
                        continue
                    flag_uses_after(
                        arg.id, stmt.lineno, "JG006",
                        lambda ln, a=arg.id, c=callee, sl=stmt.lineno: (
                            f"{a!r} was donated to {c}(...) at line {sl} "
                            f"(donate_argnums) and read again — the "
                            f"buffer may already be aliased by the "
                            f"outputs; copy what you need before the "
                            f"call or rebind the result"))

    def _own_nodes(self, fn) -> list[ast.AST]:
        """Nodes of ``fn``'s body, excluding nested function/class scopes."""
        out = []

        def rec(node):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.Lambda, ast.ClassDef)):
                    continue
                out.append(child)
                rec(child)
        for stmt in fn.body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            out.append(stmt)
            rec(stmt)
        return out

    def _stmt_targets(self, call: ast.Call, fn) -> set[str]:
        """Names assigned by the statement containing ``call``."""
        for node in ast.walk(fn):
            if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                if any(c is call for c in ast.walk(node)):
                    targets = node.targets if isinstance(node, ast.Assign) \
                        else [node.target]
                    names = set()
                    for t in targets:
                        for n in ast.walk(t):
                            if isinstance(n, ast.Name):
                                names.add(n.id)
                    return names
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                if any(c is call for c in ast.walk(node.iter)):
                    return {n.id for n in ast.walk(node.target)
                            if isinstance(n, ast.Name)}
        return set()


def analyze_source(path: str, source: str,
                   select: set[str] | None = None) -> list[Finding]:
    return Analyzer(path, source, select=select).run()
