"""Registry checkers: telemetry-name consistency and precision f32 pins.

Telemetry names are stringly-typed and cross ~15 producer modules, two
export consumers, the health endpoints and the benchmark summarizers; a
typo silently produces a parallel metric nobody reads. The single source
of truth is ``telemetry.METRIC_NAMES`` / ``METRIC_PREFIXES`` (read here
*from the AST*, so the lint suite never imports repo code):

``telemetry-undeclared-name``
    A producer call (``telemetry.counter/gauge/histogram("...")`` or
    ``span("...")``) whose literal name is not declared in the registry.
    Dynamic names (f-strings) must match a declared prefix family.
``telemetry-kind-mismatch``
    Producer uses a declared name with the wrong instrument kind
    (e.g. ``gauge("ps.commit.count")`` where the registry says counter).
``telemetry-unknown-consumer-name``
    A consumer module (summary/export/endpoints/tests) references a
    metric-shaped string in a declared namespace that no producer
    declares — the classic rename-producer-forget-consumer drift. Names
    the file itself fabricates (synthetic rows in tests) and fault-
    injection site ids are exempt.

``precision-f32-pin``
    The numerics contract (NUMERICS.md / precision.py): LayerNorm, final
    heads, and MoE routers compute in float32 under *every*
    PrecisionPolicy, and softmax inputs are never explicitly downcast.
    Flags ``nn.LayerNorm``/head/router ``nn.Dense`` calls without
    ``dtype=jnp.float32`` in models/ and ops/.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from distkeras_tpu.analysis.core import (Checker, Finding, ModuleInfo,
                                         dotted_name)

_TELEMETRY_MODULE = "distkeras_tpu/telemetry.py"
_KIND_METHODS = {"counter": "counter", "gauge": "gauge",
                 "histogram": "histogram", "span": "span"}
_METRIC_SHAPE = re.compile(r"[a-z][a-z0-9_]*(\.[a-z0-9_]+)+")

# consumers scanned for dangling metric references (besides tests/)
_CONSUMER_PATHS = (
    "distkeras_tpu/health/summary.py",
    "distkeras_tpu/profiling/cost_model.py",
    "distkeras_tpu/profiling/roofline.py",
    "distkeras_tpu/health/export.py",
    "distkeras_tpu/health/endpoints.py",
    "distkeras_tpu/health/slo.py",
    "distkeras_tpu/health/recorder.py",
    "distkeras_tpu/health/cli.py",
    "distkeras_tpu/health/timeseries.py",
)
_FAULT_FUNCS = {"inject", "apply", "clear_injections",
                "inject_chaos", "chaos", "clear_chaos"}


def _literal_dict(tree: ast.AST, name: str) -> Dict[str, str]:
    """Module-level ``NAME = {"k": "v", ...}`` literal, else empty."""
    for node in ast.iter_child_nodes(tree):
        if not isinstance(node, ast.Assign):
            continue
        if not any(isinstance(t, ast.Name) and t.id == name
                   for t in node.targets):
            continue
        if not isinstance(node.value, ast.Dict):
            continue
        out: Dict[str, str] = {}
        for k, v in zip(node.value.keys, node.value.values):
            if (isinstance(k, ast.Constant) and isinstance(k.value, str)
                    and isinstance(v, ast.Constant)
                    and isinstance(v.value, str)):
                out[k.value] = v.value
        return out
    return {}


def load_declared_names(modules: Sequence[ModuleInfo],
                        ) -> Tuple[Dict[str, str], Dict[str, str]]:
    """(METRIC_NAMES, METRIC_PREFIXES) parsed from telemetry.py's AST."""
    for mod in modules:
        if mod.relpath == _TELEMETRY_MODULE and mod.tree is not None:
            return (_literal_dict(mod.tree, "METRIC_NAMES"),
                    _literal_dict(mod.tree, "METRIC_PREFIXES"))
    return {}, {}


def _fstring_prefix(node: ast.JoinedStr) -> str:
    parts: List[str] = []
    for v in node.values:
        if isinstance(v, ast.Constant) and isinstance(v.value, str):
            parts.append(v.value)
        else:
            break
    return "".join(parts)


def _producer_calls(mod: ModuleInfo):
    """Yield (kind, name_node, call) for telemetry producer calls."""
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        target = dotted_name(node.func)
        if target is None:
            continue
        head, _, meth = target.rpartition(".")
        if not head:
            head, meth = "", target
        if meth not in _KIND_METHODS:
            continue
        # telemetry.counter(...) / bare span(...) imported from telemetry
        if head.rsplit(".", 1)[-1] != "telemetry" and not (
                head == "" and meth == "span"):
            continue
        yield _KIND_METHODS[meth], node.args[0], node


def _fault_sites(modules: Sequence[ModuleInfo]) -> Set[str]:
    sites: Set[str] = set()
    for mod in modules:
        if mod.tree is None:
            continue
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            target = dotted_name(node.func)
            if target is None:
                continue
            if target.rsplit(".", 1)[-1] in _FAULT_FUNCS:
                a = node.args[0]
                if isinstance(a, ast.Constant) and isinstance(a.value, str):
                    sites.add(a.value)
    return sites


class TelemetryRegistryChecker(Checker):
    name = "telemetry-registry"
    rules = ("telemetry-undeclared-name", "telemetry-kind-mismatch",
             "telemetry-unknown-consumer-name")

    PRODUCER_SCOPE = ("distkeras_tpu/",)

    def check(self, modules: List[ModuleInfo]) -> List[Finding]:
        if not any(m.relpath == _TELEMETRY_MODULE for m in modules):
            return []  # tree without a telemetry module: nothing to check
        declared, prefixes = load_declared_names(modules)
        out: List[Finding] = []
        if not declared:
            out.append(Finding(
                "telemetry-undeclared-name", _TELEMETRY_MODULE, 1, 0,
                "METRIC_NAMES literal dict not found in telemetry.py — "
                "the registry is the single source of metric names"))
            return out
        fault_sites = _fault_sites(modules)
        namespaces = {n.split(".", 1)[0] for n in declared}
        namespaces |= {p.split(".", 1)[0] for p in prefixes}

        for mod in modules:
            if mod.tree is None:
                continue
            if (mod.relpath.startswith(self.PRODUCER_SCOPE)
                    and mod.relpath != _TELEMETRY_MODULE):
                out.extend(self._check_producers(mod, declared, prefixes))
            if (mod.relpath in _CONSUMER_PATHS
                    or mod.relpath.startswith("tests/")):
                out.extend(self._check_consumers(
                    mod, declared, prefixes, namespaces, fault_sites))
        return out

    def _check_producers(self, mod: ModuleInfo, declared: Dict[str, str],
                         prefixes: Dict[str, str]) -> List[Finding]:
        out: List[Finding] = []
        for kind, name_node, call in _producer_calls(mod):
            loc = (call.lineno, call.col_offset)
            if isinstance(name_node, ast.Constant) and isinstance(
                    name_node.value, str):
                name = name_node.value
                if name in declared:
                    want = declared[name]
                    if want != kind:
                        out.append(Finding(
                            "telemetry-kind-mismatch", mod.relpath, *loc,
                            f"\"{name}\" is declared as a {want} but "
                            f"produced as a {kind}"))
                elif not any(name.startswith(p) for p in prefixes):
                    out.append(Finding(
                        "telemetry-undeclared-name", mod.relpath, *loc,
                        f"metric \"{name}\" is not declared in "
                        "telemetry.METRIC_NAMES — declare it once there"))
            elif isinstance(name_node, ast.JoinedStr):
                literal = _fstring_prefix(name_node)
                if not any(literal.startswith(p) or p.startswith(literal)
                           for p in prefixes):
                    out.append(Finding(
                        "telemetry-undeclared-name", mod.relpath, *loc,
                        f"dynamic metric name (f-string prefix "
                        f"\"{literal}\") matches no declared prefix "
                        "family in telemetry.METRIC_PREFIXES"))
        return out

    def _check_consumers(self, mod: ModuleInfo, declared: Dict[str, str],
                         prefixes: Dict[str, str], namespaces: Set[str],
                         fault_sites: Set[str]) -> List[Finding]:
        local: Set[str] = set()
        for kind, name_node, _ in _producer_calls(mod):
            if isinstance(name_node, ast.Constant) and isinstance(
                    name_node.value, str):
                local.add(name_node.value)
        # synthetic rows ({"name": "..."} dict literals) are file-local
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Dict):
                for k, v in zip(node.keys, node.values):
                    if (isinstance(k, ast.Constant)
                            and k.value in ("name", "site")
                            and isinstance(v, ast.Constant)
                            and isinstance(v.value, str)):
                        local.add(v.value)

        out: List[Finding] = []
        seen: Set[str] = set()
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Constant)
                    and isinstance(node.value, str)):
                continue
            s = node.value
            if s in seen or not _METRIC_SHAPE.fullmatch(s):
                continue
            # dotted-path artifacts, not metric names
            if s.endswith((".json", ".jsonl", ".log", ".txt", ".csv",
                           ".md", ".py", ".cc", ".prom")):
                continue
            if s.split(".", 1)[0] not in namespaces:
                continue
            if (s in declared or s in local or s in fault_sites
                    or any(s.startswith(p) for p in prefixes)):
                seen.add(s)
                continue
            # prefix-style reference: "health.worker." or a strict prefix
            # of a declared name used with startswith()
            if any(d.startswith(s) for d in declared):
                seen.add(s)
                continue
            seen.add(s)
            out.append(Finding(
                "telemetry-unknown-consumer-name", mod.relpath,
                node.lineno, node.col_offset,
                f"consumer references metric \"{s}\" which no producer "
                "declares in telemetry.METRIC_NAMES — renamed producer or "
                "typo'd consumer"))
        return out


# ---------------------------------------------------------------------------
# precision pinning


def _kw(call: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _is_f32(node: Optional[ast.expr]) -> bool:
    if node is None:
        return False
    name = dotted_name(node)
    return bool(name) and name.rsplit(".", 1)[-1] == "float32"


class PrecisionPinChecker(Checker):
    name = "precision"
    rules = ("precision-f32-pin",)

    SCOPE = ("distkeras_tpu/models/", "distkeras_tpu/ops/")
    PINNED_DENSE_NAMES = ("head", "router")

    def check(self, modules: List[ModuleInfo]) -> List[Finding]:
        out: List[Finding] = []
        for mod in modules:
            if mod.tree is None or not mod.relpath.startswith(self.SCOPE):
                continue
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                target = dotted_name(node.func)
                if target is None:
                    continue
                base = target.rsplit(".", 1)[-1]
                loc = (node.lineno, node.col_offset)
                if base == "LayerNorm":
                    if not _is_f32(_kw(node, "dtype")):
                        out.append(Finding(
                            "precision-f32-pin", mod.relpath, *loc,
                            "LayerNorm must pin dtype=jnp.float32: the "
                            "numerics contract keeps normalization "
                            "statistics in f32 under every "
                            "PrecisionPolicy"))
                elif base == "Dense":
                    nm = _kw(node, "name")
                    if (isinstance(nm, ast.Constant)
                            and isinstance(nm.value, str)
                            and any(p in nm.value for p in
                                    self.PINNED_DENSE_NAMES)):
                        if not _is_f32(_kw(node, "dtype")):
                            out.append(Finding(
                                "precision-f32-pin", mod.relpath, *loc,
                                f"Dense(name=\"{nm.value}\") is a "
                                "head/router op and must pin "
                                "dtype=jnp.float32 under every "
                                "PrecisionPolicy"))
                elif base == "softmax":
                    for arg in node.args:
                        for sub in ast.walk(arg):
                            if (isinstance(sub, ast.Call)
                                    and isinstance(sub.func, ast.Attribute)
                                    and sub.func.attr == "astype"
                                    and sub.args
                                    and not _is_f32(sub.args[0])):
                                out.append(Finding(
                                    "precision-f32-pin", mod.relpath,
                                    sub.lineno, sub.col_offset,
                                    "softmax input is explicitly downcast "
                                    "— attention/router softmax must "
                                    "compute in f32 (cast the *output* "
                                    "back instead)"))
        return out
