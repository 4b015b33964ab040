"""jit-purity / recompile-hazard checker.

A function handed to a JAX tracer (``jax.jit``, ``shard_map``, ``lax.scan``
/ ``while_loop`` / ``cond`` / ``fori_loop``, ``pl.pallas_call``) executes
its Python body exactly once, at trace time. Host-side effects inside it —
``time.time()``, ``np.random``, ``print``, ``.item()``, mutation of
closed-over lists/dicts — either bake a stale value into the compiled
program or silently run once instead of per step. Python ``if``/``while``
on a traced argument is the classic recompile/ConcretizationError hazard.

Rules
-----
``jit-host-effect``
    A call with host-visible side effects inside a traced function body
    (including functions lexically nested in one — they trace too).
``jit-closure-mutation``
    Mutation of a closed-over container (``xs.append(...)``, ``d[k] = v``
    on a free variable) inside a traced function.
``jit-tracer-branch``
    ``if``/``while`` whose test reads a parameter of the traced function
    (one-hop taint through local assignments). Shape/dtype/ndim reads kill
    the taint — branching on static properties is jit-safe.

Traced-function discovery is lexical: decorators (``@jax.jit``,
``@partial(jax.jit, ...)``), direct wrapping (``step = jax.jit(step)``),
and callables passed in first position to scan/shard_map/pallas_call (names
resolved against same-scope ``def``s, plus inline lambdas).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from distkeras_tpu.analysis.core import (Checker, Finding, ModuleInfo,
                                         dotted_name)

# call targets that wrap their *first* callable argument in a trace
_TRACING_WRAPPERS = {
    "jax.jit", "jit", "pjit", "jax.pjit",
    "shard_map", "jax.experimental.shard_map.shard_map",
    "pl.pallas_call", "pallas_call",
    "jax.lax.scan", "lax.scan", "jax.lax.while_loop", "lax.while_loop",
    "jax.lax.fori_loop", "lax.fori_loop", "jax.lax.cond", "lax.cond",
    "jax.lax.map", "lax.map", "jax.checkpoint", "jax.remat",
    "jax.grad", "jax.value_and_grad", "jax.vmap", "jax.pmap",
}
# decorator spellings (bare attribute or partial(<wrapper>, ...))
_TRACING_DECORATORS = {"jax.jit", "jit", "pjit", "jax.pjit", "jax.pmap",
                       "jax.vmap", "jax.checkpoint", "jax.remat"}

# host-effect call prefixes / exact dotted names
_HOST_EFFECT_CALLS = {
    "time.time", "time.perf_counter", "time.monotonic", "time.sleep",
    "time.process_time",
    "print", "input", "open", "breakpoint",
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
}
_HOST_EFFECT_PREFIXES = (
    "np.random.", "numpy.random.", "random.",
    "os.", "sys.", "logging.", "telemetry.", "warnings.",
)
# method names on arbitrary receivers that force a device sync / host copy
_HOST_EFFECT_METHODS = {"item", "tolist", "block_until_ready"}
_MUTATING_METHODS = {"append", "extend", "insert", "pop", "remove", "clear",
                     "update", "setdefault", "popitem", "add", "discard"}
# receivers for which _HOST_EFFECT_PREFIXES should NOT fire
_PURE_PREFIX_ALLOW = ("jax.random.", "jax.", "jnp.", "lax.", "nn.")
# shape/dtype reads are static under tracing: they kill branch taint
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size"}


def _static_names(call: Optional[ast.Call], fn: ast.AST) -> Set[str]:
    """Parameters declared static via static_argnames/static_argnums in a
    jit wrapper call — branching on them is jit-legal (Python-level)."""
    if call is None:
        return set()
    out: Set[str] = set()
    pos = [p.arg for p in getattr(getattr(fn, "args", None), "args", [])]
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            vals = (kw.value.elts if isinstance(kw.value,
                                                (ast.Tuple, ast.List))
                    else [kw.value])
            for v in vals:
                if isinstance(v, ast.Constant) and isinstance(v.value, str):
                    out.add(v.value)
        elif kw.arg == "static_argnums":
            vals = (kw.value.elts if isinstance(kw.value,
                                                (ast.Tuple, ast.List))
                    else [kw.value])
            for v in vals:
                if (isinstance(v, ast.Constant)
                        and isinstance(v.value, int)
                        and 0 <= v.value < len(pos)):
                    out.add(pos[v.value])
    return out


def _decorator_traces(dec: ast.expr) -> Optional[ast.Call]:
    """The configuring Call node when the decorator traces (for static
    argname extraction), a sentinel bare marker otherwise, None if not."""
    name = dotted_name(dec)
    if name in _TRACING_DECORATORS:
        return ast.Call(func=dec, args=[], keywords=[])
    if isinstance(dec, ast.Call):
        inner = dotted_name(dec.func)
        if inner in _TRACING_DECORATORS:
            return dec
        if inner in ("partial", "functools.partial") and dec.args:
            if dotted_name(dec.args[0]) in _TRACING_WRAPPERS:
                return dec
    return None


class _ScopeIndex:
    """Map (scope-node id, name) -> FunctionDef for lexical resolution of
    names passed to tracing wrappers (``jax.jit(step)``)."""

    def __init__(self) -> None:
        self.defs: Dict[Tuple[int, str], ast.AST] = {}

    def index(self, tree: ast.AST) -> None:
        self._walk(tree)

    def _walk(self, node: ast.AST) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.defs[(id(node), child.name)] = child
            self._walk(child)


def _collect_traced(tree: ast.AST) -> List[ast.AST]:
    """Return function nodes (FunctionDef or Lambda) that are traced."""
    index = _ScopeIndex()
    index.index(tree)

    # parent-scope map: every node -> nearest enclosing function/module
    scope_of: Dict[int, ast.AST] = {}

    def assign_scopes(node: ast.AST, scope: ast.AST) -> None:
        for child in ast.iter_child_nodes(node):
            scope_of[id(child)] = scope
            next_scope = scope
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                next_scope = child
            assign_scopes(child, next_scope)

    assign_scopes(tree, tree)

    traced: List[Tuple[ast.AST, Set[str]]] = []
    seen: Set[int] = set()

    def mark(fn: ast.AST, static: Set[str]) -> None:
        if id(fn) not in seen:
            seen.add(id(fn))
            traced.append((fn, static))

    def resolve(name: str, at: ast.AST) -> Optional[ast.AST]:
        scope: Optional[ast.AST] = scope_of.get(id(at), tree)
        while scope is not None:
            fn = index.defs.get((id(scope), name))
            if fn is not None:
                return fn
            scope = scope_of.get(id(scope))
        return index.defs.get((id(tree), name))

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for d in node.decorator_list:
                call = _decorator_traces(d)
                if call is not None:
                    mark(node, _static_names(call, node))
                    break
        elif isinstance(node, ast.Call):
            target = dotted_name(node.func)
            if target in _TRACING_WRAPPERS and node.args:
                arg = node.args[0]
                if isinstance(arg, ast.Lambda):
                    mark(arg, _static_names(node, arg))
                elif isinstance(arg, ast.Name):
                    fn = resolve(arg.id, node)
                    if fn is not None and not isinstance(fn, ast.Module):
                        mark(fn, _static_names(node, fn))
    return traced


def _bound_names(fn: ast.AST) -> Set[str]:
    """Names bound inside fn: params + assignment/for/with/comprehension
    targets (anything NOT in here that gets mutated is closed-over)."""
    bound: Set[str] = set()
    if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
        a = fn.args
        for p in (list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)):
            bound.add(p.arg)
        if a.vararg:
            bound.add(a.vararg.arg)
        if a.kwarg:
            bound.add(a.kwarg.arg)
    for node in ast.walk(fn):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Store):
            bound.add(node.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node is not fn:
                bound.add(node.name)
    return bound


def _params(fn: ast.AST) -> Set[str]:
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.Lambda)):
        return set()
    a = fn.args
    names = {p.arg for p in
             (list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs))}
    if a.vararg:
        names.add(a.vararg.arg)
    if a.kwarg:
        names.add(a.kwarg.arg)
    names.discard("self")
    return names


def _expr_taints(expr: ast.expr, tainted: Set[str]) -> bool:
    """True when expr reads a tainted name WITHOUT passing through a
    static-property access (.shape/.ndim/.dtype, len(), isinstance)."""
    for node in ast.walk(expr):
        if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
            return _strip(expr, node, tainted)
        if (isinstance(node, ast.Call)
                and dotted_name(node.func) in ("len", "isinstance",
                                               "hasattr", "type")):
            return _strip(expr, node, tainted)
    return any(isinstance(n, ast.Name) and n.id in tainted
               for n in ast.walk(expr))


def _strip(expr: ast.expr, skip: ast.AST, tainted: Set[str]) -> bool:
    """Re-check the expression with the static-access subtree removed."""
    skipped = set(id(n) for n in ast.walk(skip))
    for node in ast.walk(expr):
        if id(node) in skipped:
            continue
        if isinstance(node, ast.Name) and node.id in tainted:
            return True
    return False


class JitPurityChecker(Checker):
    name = "jit-purity"
    rules = ("jit-host-effect", "jit-closure-mutation", "jit-tracer-branch")

    SCOPE = ("distkeras_tpu/",)

    def check(self, modules: List[ModuleInfo]) -> List[Finding]:
        out: List[Finding] = []
        dedup: Set[Tuple[str, str, int, int]] = set()
        for mod in modules:
            if mod.tree is None:
                continue
            if not mod.relpath.startswith(self.SCOPE):
                continue
            for fn, static in _collect_traced(mod.tree):
                # nested traced defs are walked through their parent too;
                # dedupe on (rule, location)
                for f in self._check_fn(mod, fn, static):
                    key = (f.rule, f.path, f.line, f.col)
                    if key not in dedup:
                        dedup.add(key)
                        out.append(f)
        return out

    def _check_fn(self, mod: ModuleInfo, fn: ast.AST,
                  static: Set[str]) -> List[Finding]:
        out: List[Finding] = []
        bound = _bound_names(fn)
        body = fn.body if isinstance(fn.body, list) else [fn.body]

        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                out.extend(self._check_call(mod, node, bound))
            elif isinstance(node, (ast.Global, ast.Nonlocal)):
                out.append(Finding(
                    "jit-closure-mutation", mod.relpath, node.lineno,
                    node.col_offset,
                    f"`{type(node).__name__.lower()}` rebinding inside a "
                    "traced function runs at trace time, not per step"))
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    if isinstance(t, ast.Subscript):
                        base = t.value
                        name = base.id if isinstance(base, ast.Name) else None
                        if name is not None and name not in bound:
                            out.append(Finding(
                                "jit-closure-mutation", mod.relpath,
                                node.lineno, node.col_offset,
                                f"subscript-assignment into closed-over "
                                f"`{name}` inside a traced function is a "
                                "host-side mutation (happens once, at "
                                "trace time)"))

        out.extend(self._check_branches(mod, fn, static))
        return out

    def _check_call(self, mod: ModuleInfo, node: ast.Call,
                    bound: Set[str]) -> List[Finding]:
        target = dotted_name(node.func)
        line, col = node.lineno, node.col_offset
        if target is not None:
            if target in _HOST_EFFECT_CALLS:
                return [Finding("jit-host-effect", mod.relpath, line, col,
                                f"call to `{target}` inside a traced "
                                "function executes at trace time (stale "
                                "value baked into the compiled program)")]
            if (target.startswith(_HOST_EFFECT_PREFIXES)
                    and not target.startswith(_PURE_PREFIX_ALLOW)):
                return [Finding("jit-host-effect", mod.relpath, line, col,
                                f"host-side call `{target}` inside a traced "
                                "function (runs once at trace, not per "
                                "step)")]
        if isinstance(node.func, ast.Attribute):
            meth = node.func.attr
            recv = node.func.value
            recv_name = recv.id if isinstance(recv, ast.Name) else None
            if meth in _HOST_EFFECT_METHODS:
                return [Finding("jit-host-effect", mod.relpath, line, col,
                                f"`.{meth}()` inside a traced function "
                                "forces a host transfer / fails on "
                                "tracers")]
            # .update(a, b, ...) with 2+ positional args is the optax
            # GradientTransformation API (pure), not dict.update
            if (meth in _MUTATING_METHODS and recv_name is not None
                    and recv_name not in bound
                    and not (meth == "update" and len(node.args) >= 2)):
                return [Finding("jit-closure-mutation", mod.relpath, line,
                                col,
                                f"`{recv_name}.{meth}(...)` mutates a "
                                "closed-over container inside a traced "
                                "function (runs at trace time only)")]
        return []

    def _check_branches(self, mod: ModuleInfo, fn: ast.AST,
                        static: Set[str]) -> List[Finding]:
        params = _params(fn) - static
        if not params:
            return []
        # one-hop taint: locals assigned from expressions reading a param
        tainted = set(params)
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and _expr_taints(node.value,
                                                             tainted):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        tainted.add(t.id)
        out: List[Finding] = []
        for node in ast.walk(fn):
            if isinstance(node, (ast.If, ast.While)):
                if _expr_taints(node.test, tainted):
                    kind = "if" if isinstance(node, ast.If) else "while"
                    out.append(Finding(
                        "jit-tracer-branch", mod.relpath, node.lineno,
                        node.col_offset,
                        f"Python `{kind}` on a traced value — raises "
                        "ConcretizationError under jit or forces a "
                        "recompile per value; use lax.cond/lax.while_loop "
                        "or branch on static shape/dtype"))
        return out
