"""Per-op cost inventory of a compiled executable.

jax exposes two views of a compiled computation: an aggregate
``cost_analysis()`` dict (flops / bytes accessed, whole-program) and the
post-optimization HLO text via ``as_text()``. There is no structured
per-op cost API, so the inventory here walks the HLO text: one row per
entry-computation instruction, fusions kept as single rows (their internal
producer/consumer traffic never touches HBM, so the fusion's own operand +
output bytes ARE the memory-traffic model), called computations expanded
inline, ``while`` bodies counted once unless the caller supplies the trip
count (same floor contract as ``observability.count_flops`` documents for
dynamic trips).

Honest limits (DESIGN.md §21): FLOPs follow the 2*MAC convention for
dot/convolution and 1/elem for elementwise; bytes are *shape arithmetic*
over operand and output types — XLA's-estimate-style traffic, not measured
DMA counters. When a backend yields no HLO text or no parseable ops, the
condition is recorded ONCE per process (``profile.op.inventory_unavailable``)
and a typed empty inventory is returned — the same degrade-don't-lie rule
as PR 1's ``compiled_flops``.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from distkeras_tpu import telemetry

# dtype -> bytes per element, covering everything XLA emits in practice.
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e5m2fnuz": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 1, "u4": 1, "pred": 1,
    "c64": 8, "c128": 16, "token": 0, "opaque": 0, "tuple": 0,
}

# Opcodes that move or reinterpret data without arithmetic: zero FLOPs.
_ZERO_FLOP = frozenset({
    "parameter", "constant", "copy", "copy-start", "copy-done", "bitcast",
    "bitcast-convert", "reshape", "transpose", "broadcast", "slice",
    "dynamic-slice", "dynamic-update-slice", "concatenate", "pad",
    "get-tuple-element", "tuple", "iota", "reverse", "gather",
    "all-gather", "all-to-all", "collective-permute", "partition-id",
    "replica-id", "infeed", "outfeed", "send", "recv", "send-done",
    "recv-done", "after-all", "domain", "rng-bit-generator",
    "get-dimension-size", "optimization-barrier", "custom-call",
})

# Per-input-element arithmetic (reductions and friends).
_PER_INPUT_ELEM = frozenset({
    "reduce", "reduce-window", "select-and-scatter", "scatter", "map",
    "sort", "all-reduce", "reduce-scatter", "cholesky", "triangular-solve",
})

# Instructions whose called computations are expanded inline.
_EXPAND_CALLS = frozenset({"call", "while", "conditional", "fusion"})

_instr_re = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<type>\([^=]*?\)|[\w\[\]{},:#*\s()]+?)\s+"
    r"(?P<opcode>[\w\-]+)\(")
# (the parentheses in the type's class are the TPU's tiled layouts,
# ``bf16[8,128]{1,0:T(8,128)(2,1)S(1)}``: without them only tuple-typed
# instructions of a TPU executable parsed)
_comp_re = re.compile(
    r"^(?:ENTRY\s+)?%?(?P<name>[\w.\-]+)\s*(?:\(.*\)\s*->|\{)")
_shape_re = re.compile(r"(?P<dtype>[a-z]\w*)\[(?P<dims>[\d,]*)\]")
_opname_re = re.compile(r'op_name="([^"]*)"')
_calls_re = re.compile(
    r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_branches_re = re.compile(r"branch_computations=\{([^}]*)\}")
# long tuple types carry /*index=N*/ position comments whose '=' breaks
# the type group of _instr_re — strip them before matching
_comment_re = re.compile(r"/\*.*?\*/")


def _shape_bytes_elems(type_str: str) -> Tuple[float, float]:
    """(bytes, elements) of an HLO type string; tuples sum components."""
    total_b = total_e = 0.0
    for m in _shape_re.finditer(type_str):
        dims = m.group("dims")
        elems = 1.0
        for d in dims.split(","):
            if d.strip():
                elems *= int(d)
        total_e += elems
        total_b += elems * _DTYPE_BYTES.get(m.group("dtype"), 4)
    return total_b, total_e


def _out_dtype(type_str: str) -> str:
    m = _shape_re.search(type_str)
    return m.group("dtype") if m else "f32"


def _split_operands(rest: str) -> Tuple[str, str]:
    """Split ``...operands), attrs`` at the operand-list closing paren
    (operand types may nest parens for tuple shapes)."""
    depth = 1
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return rest[:i], rest[i + 1:]
    return rest, ""


def _attr_dims(attrs: str, key: str) -> List[int]:
    m = re.search(key + r"=\{([\d,\s]*)\}", attrs)
    if not m:
        return []
    return [int(x) for x in m.group(1).split(",") if x.strip()]


def _split_args(operands: str) -> List[str]:
    """Top-level comma split of an operand list (tuple types nest)."""
    out, depth, start = [], 0, 0
    for i, ch in enumerate(operands):
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        elif ch == "," and depth == 0:
            out.append(operands[start:i])
            start = i + 1
    tail = operands[start:].strip()
    if tail:
        out.append(operands[start:])
    return out


def _resolve_operands(operands: str, types: Dict[str, str]) -> str:
    """Operand list with every bare name replaced by its producer's type.

    Post-optimization HLO prints operand types inline
    (``dot(f32[8,16]{1,0} %a, ...)``); pre-optimization text prints bare
    names (``dot(Arg_0.1, ...)``) — resolve those through the module-wide
    name -> out_type map so shape arithmetic works on both dialects."""
    parts = []
    for tok in _split_args(operands):
        if _shape_re.search(tok):
            parts.append(tok)
            continue
        name = tok.strip().lstrip("%")
        parts.append(types.get(name, ""))
    return ", ".join(parts)


_scope_token_re = re.compile(r"[\w.\-]+")


def _scope(attrs: str, declared) -> str:
    """The innermost of the ``declared`` scope names on the instruction's
    ``op_name`` path (``jit(prefill)/.../attn.latent/attn.index/dot_general``
    -> ``attn.index``), ``""`` where the path holds none or there is no
    path. A segment a transformation wrapped (``transpose(jvp(attn.qkv))``)
    counts as the name inside it."""
    m = _opname_re.search(attrs) if declared else None
    if not m:
        return ""
    for seg in reversed(m.group(1).split("/")):
        if seg in declared:
            return seg
        if "(" in seg:
            for token in reversed(_scope_token_re.findall(seg)):
                if token in declared:
                    return token
    return ""


_ref_re = re.compile(r"%([\w.\-]+)")
#: bookkeeping: no device event, no row
_PLUMBING = frozenset({"parameter", "constant", "get-tuple-element", "tuple"})
#: of those, what hands no scope on either: a tuple gathers results of
#: every scope (a ``get-tuple-element`` is looked through)
_CARRIES_NONE = _PLUMBING - {"get-tuple-element"}


def _scopes_of(comps: Dict[str, List["_Instr"]], declared
               ) -> Dict[str, Tuple[str, bool]]:
    """``name -> (scope, inferred)`` for every instruction of the module.

    An instruction's scope is :func:`_scope` of its own ``op_name`` path;
    a fusion without one takes that of the instruction nearest its root
    that names one. What is left are the instructions the compiler made
    and gave no path at all (a relayout copy or convert of a weight, a
    prefetch's ``copy-start``/``copy-done``, a rewritten ``dot``): those
    take, within their computation, the scope of the nearest instruction
    that produces one of their operands and, failing that, of the nearest
    that uses their result, and are marked ``inferred``. An instruction
    that has a path with no declared name on it stays ``""``: it was
    traced outside every scope, and that is the finding."""
    out: Dict[str, Tuple[str, bool]] = {}
    if not declared:
        return out
    for instrs in comps.values():
        producers: Dict[str, List[str]] = {}
        users: Dict[str, List[str]] = {}
        local = {ins.name for ins in instrs}
        pathless = []
        for ins in instrs:
            refs = [r for r in _ref_re.findall(ins.operands) if r in local]
            producers[ins.name] = refs
            for r in refs:
                users.setdefault(r, []).append(ins.name)
            scope = _scope(ins.attrs, declared)
            has_path = bool(_opname_re.search(ins.attrs))
            if ins.opcode == "fusion" and not scope:
                inside = [i for c in _calls_re.findall(ins.attrs)
                          for i in comps.get(c, [])]
                scope = next(filter(None, (_scope(i.attrs, declared)
                                           for i in reversed(inside))), "")
                has_path = has_path or any(
                    _opname_re.search(i.attrs) for i in inside
                    if i.opcode not in _PLUMBING)
            if scope or has_path:
                out[ins.name] = (scope, False)
            elif ins.opcode not in _CARRIES_NONE:
                pathless.append(ins.name)
        for near in (producers, users, producers):
            changed = True
            while changed:
                changed = False
                for name in pathless:
                    if name in out:
                        continue
                    found = next((out[n][0] for n in near.get(name, ())
                                  if out.get(n, ("",))[0]), "")
                    if found:
                        out[name] = (found, True)
                        changed = True
        for name in pathless:
            out.setdefault(name, ("", False))
    return out


def _source(attrs: str) -> str:
    """Model-source annotation: trailing segments of the op_name metadata
    path (``jit(window_fn)/.../transpose(jvp(conv))/conv_general``)."""
    m = _opname_re.search(attrs)
    if not m:
        return ""
    segs = [s for s in m.group(1).split("/") if not s.startswith("jit(")]
    return "/".join(segs[-2:]) if segs else ""


@dataclass
class OpCost:
    """One costed HLO instruction (or one fusion, kept whole)."""
    name: str
    opcode: str
    flops: float
    bytes_accessed: float
    output_bytes: float
    dtype: str = "f32"
    source: str = ""
    fusion_ops: Tuple[str, ...] = ()
    count: int = 1  # >1 after by-source grouping
    #: the output type as the text has it, layout included
    #: (``bf16[33,1024]{1,0:T(8,128)}``, ``(f32[8]{0}, s32[]) ``): with
    #: ``name`` it is what a profiler's device event calls the instruction
    out_type: str = ""
    #: innermost declared ``jax.named_scope`` on the ``op_name`` path
    #: (:func:`_scope`); ``""`` where none, or none were declared
    scope: str = ""
    #: the compiler made this instruction and gave it no path: ``scope`` is
    #: its neighbour's (:func:`_scopes_of`)
    scope_inferred: bool = False

    @property
    def intensity(self) -> Optional[float]:
        """Arithmetic intensity, FLOPs per HBM byte (None for pure data
        movement — no arithmetic to bound)."""
        if self.bytes_accessed <= 0:
            return None
        return self.flops / self.bytes_accessed


@dataclass
class OpInventory:
    """Typed inventory of an executable's ops. ``available=False`` is the
    honest no-cost-model-on-this-backend result: zero rows plus a note,
    never a fabricated table."""
    rows: List[OpCost] = field(default_factory=list)
    available: bool = True
    note: str = ""
    xla_flops: Optional[float] = None   # cost_analysis() aggregate
    xla_bytes: Optional[float] = None
    while_floor: bool = False  # a while body was counted at trips=1

    @property
    def total_flops(self) -> float:
        return sum(r.flops for r in self.rows)

    @property
    def total_bytes(self) -> float:
        return sum(r.bytes_accessed for r in self.rows)

    def by_source(self) -> List[OpCost]:
        """Rows aggregated by (opcode, model-source annotation) — the view
        a human reads: '27 conv ops from resnet blocks' as one line."""
        groups: Dict[Tuple[str, str], OpCost] = {}
        for r in self.rows:
            key = (r.opcode, r.source)
            g = groups.get(key)
            if g is None:
                groups[key] = OpCost(
                    name=r.source or r.opcode, opcode=r.opcode,
                    flops=r.flops, bytes_accessed=r.bytes_accessed,
                    output_bytes=r.output_bytes, dtype=r.dtype,
                    source=r.source, fusion_ops=r.fusion_ops, count=1)
            else:
                g.flops += r.flops
                g.bytes_accessed += r.bytes_accessed
                g.output_bytes += r.output_bytes
                g.count += 1
        return sorted(groups.values(), key=lambda g: -g.flops)


@dataclass
class _Instr:
    name: str
    opcode: str
    out_type: str
    operands: str
    attrs: str


def _parse_computations(hlo_text: str) -> Tuple[
        Optional[str], Dict[str, List[_Instr]], Dict[str, str]]:
    """Split HLO text into computations; returns (entry_name, comp map,
    module-wide instruction-name -> out_type map)."""
    comps: Dict[str, List[_Instr]] = {}
    entry = None
    current: Optional[List[_Instr]] = None
    for line in hlo_text.splitlines():
        line = _comment_re.sub("", line)
        stripped = line.strip()
        if not stripped or stripped.startswith(("HloModule", "//", "#")):
            continue
        if stripped.endswith("{") and "=" not in stripped.split("(")[0]:
            m = _comp_re.match(stripped)
            if m:
                name = m.group("name")
                current = comps.setdefault(name, [])
                if stripped.startswith("ENTRY"):
                    entry = name
                continue
        if stripped == "}":
            current = None
            continue
        if current is None:
            continue
        m = _instr_re.match(line)
        if not m:
            continue
        operands, attrs = _split_operands(line[m.end():])
        current.append(_Instr(
            name=m.group("name"), opcode=m.group("opcode"),
            out_type=m.group("type").strip(), operands=operands,
            attrs=attrs))
    types = {ins.name: ins.out_type
             for instrs in comps.values() for ins in instrs}
    return entry, comps, types


def _win_vals(attrs: str, key: str, n: int, default: int) -> List[int]:
    """Per-spatial-dim window attribute (``stride=2x2`` ->  [2, 2]);
    ``pad`` entries are lo_hi pairs and are returned as-is strings split
    elsewhere."""
    m = re.search(key + r"=([\d_x]+)", attrs)
    if not m:
        return [default] * n
    vals = [x for x in m.group(1).split("x") if x.strip()]
    out = []
    for v in vals:
        out.append(int(v.split("_")[0]) if "_" in v else int(v))
    while len(out) < n:
        out.append(default)
    return out


def _win_pads(attrs: str, n: int) -> List[Tuple[int, int]]:
    m = re.search(r"pad=([\d_x]+)", attrs)
    if not m:
        return [(0, 0)] * n
    out = []
    for v in m.group(1).split("x"):
        if not v.strip():
            continue
        lo, _, hi = v.partition("_")
        out.append((int(lo), int(hi) if hi else int(lo)))
    while len(out) < n:
        out.append((0, 0))
    return out


def _conv_flops(ins: _Instr, types: Dict[str, str], out_elems: float) -> float:
    """Exact MAC count for a general convolution: per spatial dim, count
    the kernel taps that land on real (non-padding, non-dilation-zero)
    input for every output position. Shape arithmetic alone overcounts
    padding taps and base-dilation zero taps — exactly the work XLA's
    split-conv / pad-elision rewrites never execute, so counting them
    would overstate the executable (DESIGN.md §21 honest limits)."""
    resolved = _resolve_operands(ins.operands, types)
    shapes = _shape_re.findall(resolved)
    out_m = _shape_re.search(ins.out_type)
    dl = re.search(r"dim_labels=(\S+?)(?:,|$)", ins.attrs)
    if len(shapes) < 2 or out_m is None or dl is None:
        return 2.0 * out_elems
    m = re.match(r"(\w+)_(\w+)->(\w+)", dl.group(1))
    if m is None:
        return 2.0 * out_elems
    lhs_l, rhs_l, out_l = m.groups()
    lhs_dims = [int(x) for x in shapes[0][1].split(",") if x.strip()]
    rhs_dims = [int(x) for x in shapes[1][1].split(",") if x.strip()]
    out_dims = [int(x) for x in out_m.group("dims").split(",") if x.strip()]
    spatial = sorted(c for c in rhs_l if c.isdigit())
    n = len(spatial)
    strides = _win_vals(ins.attrs, "stride", n, 1)
    pads = _win_pads(ins.attrs, n)
    ldil = _win_vals(ins.attrs, "lhs_dilate", n, 1)
    rdil = _win_vals(ins.attrs, "rhs_dilate", n, 1)
    try:
        taps_total = 1.0
        for d, c in enumerate(spatial):
            in_d = lhs_dims[lhs_l.index(c)]
            k_d = rhs_dims[rhs_l.index(c)]
            out_d = out_dims[out_l.index(c)]
            in_extent = (in_d - 1) * ldil[d] + 1
            if out_d * k_d > 4_000_000:  # huge dims: skip the exact loop
                taps_total *= out_d * k_d / ldil[d]
                continue
            taps = 0
            for o in range(out_d):
                base = o * strides[d] - pads[d][0]
                for k in range(k_d):
                    pos = base + k * rdil[d]
                    if 0 <= pos < in_extent and pos % ldil[d] == 0:
                        taps += 1
            taps_total *= taps
        batch = out_dims[out_l.index("b")] if "b" in out_l else 1
        out_f = out_dims[out_l.index("f")] if "f" in out_l else 1
        in_c = rhs_dims[rhs_l.index("i")] if "i" in rhs_l else 1
        return 2.0 * batch * out_f * in_c * taps_total
    except (ValueError, IndexError):
        return 2.0 * out_elems


def _instr_flops(ins: _Instr, comp_flops: Dict[str, float],
                 types: Dict[str, str]) -> float:
    """FLOPs of one instruction. 2*MAC for dot/conv, 1/elem elementwise,
    1/input-elem for reductions, called-computation total for fusion."""
    op = ins.opcode
    _, out_elems = _shape_bytes_elems(ins.out_type)
    if op in _ZERO_FLOP:
        return 0.0
    if op == "dot":
        lhs_m = _shape_re.search(_resolve_operands(ins.operands, types))
        if lhs_m is None:
            return 2.0 * out_elems
        lhs_dims = [int(x) for x in lhs_m.group("dims").split(",")
                    if x.strip()]
        k = 1.0
        for ax in _attr_dims(ins.attrs, "lhs_contracting_dims"):
            if ax < len(lhs_dims):
                k *= lhs_dims[ax]
        return 2.0 * out_elems * k
    if op == "convolution":
        return _conv_flops(ins, types, out_elems)
    if op in _PER_INPUT_ELEM:
        _, in_e = _shape_bytes_elems(
            _resolve_operands(ins.operands, types))
        return in_e
    if op in _EXPAND_CALLS:
        return 0.0  # expanded by the walker, not costed here
    # default: elementwise arithmetic at 1 FLOP per output element
    return out_elems


def parse_hlo_ops(hlo_text: str,
                  while_trips: Optional[float] = None,
                  declared=()) -> Tuple[List[OpCost], bool]:
    """Walk post-optimization HLO text into costed rows: one for every
    instruction the device runs as an event of its own (entry computation,
    loop and conditional bodies, called computations; a fusion whole, not
    its inside; no parameter, constant or tuple plumbing, and no row for a
    ``while``, ``call`` or ``conditional`` itself, whose event spans its
    body's).

    Returns ``(rows, while_floor)``; ``while_floor`` is True when a while
    body was counted once for lack of a trip count (the caller may know it
    — attribution passes the window length, since the window scan is the
    only loop in the training step). ``declared`` is the set of
    ``jax.named_scope`` names a row's ``scope`` is chosen from
    (:func:`_scopes_of`; ``profiling/scopes.py``).
    """
    declared = frozenset(declared)
    entry, comps, types = _parse_computations(hlo_text)
    scopes = _scopes_of(comps, declared)

    def scope_of(name: str) -> dict:
        scope, inferred = scopes.get(name, ("", False))
        return {"scope": scope, "scope_inferred": inferred}
    if entry is None:
        return [], False
    comp_flops: Dict[str, float] = {}

    def total_flops(comp: str, seen=()) -> float:
        if comp in comp_flops:
            return comp_flops[comp]
        if comp in seen:
            return 0.0
        total = 0.0
        for ins in comps.get(comp, []):
            if ins.opcode in _EXPAND_CALLS:
                for callee in _calls_re.findall(ins.attrs):
                    total += total_flops(callee, seen + (comp,))
            else:
                total += _instr_flops(ins, comp_flops, types)
        comp_flops[comp] = total
        return total

    rows: List[OpCost] = []
    while_floor = False

    def walk(comp: str, scale: float, seen=()) -> None:
        nonlocal while_floor
        if comp in seen:
            return
        for ins in comps.get(comp, []):
            out_b, _ = _shape_bytes_elems(ins.out_type)
            in_b, _ = _shape_bytes_elems(
                _resolve_operands(ins.operands, types))
            if ins.opcode == "fusion":
                flops = sum(total_flops(c)
                            for c in _calls_re.findall(ins.attrs))
                fused = tuple(sorted({i.opcode
                                      for c in _calls_re.findall(ins.attrs)
                                      for i in comps.get(c, [])
                                      if i.opcode not in _ZERO_FLOP}))
                rows.append(OpCost(
                    name=ins.name, opcode="fusion",
                    flops=flops * scale,
                    bytes_accessed=(in_b + out_b) * scale,
                    output_bytes=out_b * scale,
                    dtype=_out_dtype(ins.out_type),
                    source=_source(ins.attrs), fusion_ops=fused,
                    out_type=ins.out_type, **scope_of(ins.name)))
                continue
            if ins.opcode == "while":
                trips = while_trips
                if trips is None:
                    trips = 1.0
                    while_floor = True
                for callee in _calls_re.findall(ins.attrs):
                    walk(callee, scale * trips, seen + (comp,))
                continue
            if ins.opcode in ("call", "conditional"):
                callees = _calls_re.findall(ins.attrs)
                m = _branches_re.search(ins.attrs)
                if m:
                    callees += [c.strip().lstrip("%")
                                for c in m.group(1).split(",")]
                for callee in callees:
                    walk(callee, scale, seen + (comp,))
                continue
            flops = _instr_flops(ins, comp_flops, types)
            if ins.opcode in _PLUMBING:
                continue  # bookkeeping ops: not worth a row
            rows.append(OpCost(
                name=ins.name, opcode=ins.opcode, flops=flops * scale,
                bytes_accessed=(in_b + out_b) * scale,
                output_bytes=out_b * scale,
                dtype=_out_dtype(ins.out_type),
                source=_source(ins.attrs), out_type=ins.out_type,
                **scope_of(ins.name)))
    walk(entry, 1.0)
    return rows, while_floor


_inventory_noted = False


def _note_unavailable(note: str) -> OpInventory:
    """Once-per-process counter + typed empty inventory (no per-step spam,
    same rule as ``observability.compiled_flops``)."""
    global _inventory_noted
    if not _inventory_noted:
        _inventory_noted = True
        telemetry.counter("profile.op.inventory_unavailable").inc()
    return OpInventory(rows=[], available=False, note=note)


def op_inventory(compiled,
                 while_trips: Optional[float] = None) -> OpInventory:
    """Costed op inventory of a compiled executable (``jit(f).lower(...)
    .compile()``). Never raises: backends without HLO text / cost analysis
    yield a typed empty inventory with ``available=False``."""
    xla_flops = xla_bytes = None
    try:
        cost = compiled.cost_analysis()
        xla_flops = float(cost["flops"]) if cost.get("flops") else None
        xla_bytes = (float(cost["bytes accessed"])
                     if cost.get("bytes accessed") else None)
    except Exception:
        pass  # HLO text alone can still carry the inventory
    try:
        text = compiled.as_text()
    except Exception:
        return _note_unavailable("no HLO text on this backend")
    if not isinstance(text, str) or "ENTRY" not in text:
        return _note_unavailable("backend HLO dump not parseable")
    rows, while_floor = parse_hlo_ops(text, while_trips=while_trips)
    if not rows:
        return _note_unavailable("no costed ops in backend HLO")
    return OpInventory(rows=rows, available=True, xla_flops=xla_flops,
                       xla_bytes=xla_bytes, while_floor=while_floor)


def source_inventory(lowered,
                     while_trips: Optional[float] = None) -> OpInventory:
    """Costed inventory of the PRE-optimization HLO of a ``Lowered``
    (``jit(f).lower(...)``) — the model-source compute, one instruction
    per traced JAX op, before XLA fuses or rewrites anything.

    This is the honest coverage denominator for the post-optimization
    inventory: both sides are costed by the SAME shape arithmetic (the
    dilation-aware conv model included), so the ratio measures how much
    of the source compute the op table attributes — not the divergence
    between two unrelated FLOPs conventions. Never raises."""
    try:
        text = lowered.compiler_ir(dialect="hlo").as_hlo_text()
    except Exception:
        return _note_unavailable("no pre-optimization HLO on this backend")
    if not isinstance(text, str) or "ENTRY" not in text:
        return _note_unavailable("pre-optimization HLO not parseable")
    rows, while_floor = parse_hlo_ops(text, while_trips=while_trips)
    if not rows:
        return _note_unavailable("no costed ops in pre-optimization HLO")
    return OpInventory(rows=rows, available=True, while_floor=while_floor)
