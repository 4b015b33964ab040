"""Roofline classification of a costed op inventory.

The roofline model: an op needing F FLOPs and B HBM bytes runs in at best
``max(F/peak, B/bandwidth)`` seconds; its arithmetic intensity F/B decides
which term binds. Below the ridge point ``peak/bandwidth`` (FLOPs per byte)
the op is memory-bound — more MXU throughput cannot help it; above, it is
compute-bound — a faster or lower-precision matmul path can. Ops whose
modeled time sits under the dispatch floor are latency-bound: neither.

Peaks and bandwidths come from the one ``observability.DEVICE_PEAKS`` table
(fp8-sim claims the bf16 peak per the PR 6 honesty rule — it runs on the
bf16 MXU). Each top-k row carries a
"what would fix it" tag keyed to the ROADMAP item-1 candidates: Pallas
attention, real fp8 matmuls, psum/overlap co-tuning.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

from distkeras_tpu import observability, telemetry
from distkeras_tpu.profiling.cost_model import OpCost, OpInventory

#: modeled times under this are dispatch overhead, not data or flops
LATENCY_FLOOR_S = 1e-6

_COLLECTIVES = frozenset({
    "all-reduce", "reduce-scatter", "all-gather", "all-to-all",
    "collective-permute"})


def classify(flops: float, bytes_accessed: float, peak: float,
             bandwidth: float,
             latency_floor_s: float = LATENCY_FLOOR_S) -> str:
    """``"memory" | "compute" | "latency"`` for one op against one chip's
    ceilings. Pure data movement (zero FLOPs) is memory-bound by
    definition unless it is too small to even cover dispatch."""
    t_compute = flops / peak if peak > 0 else 0.0
    t_memory = bytes_accessed / bandwidth if bandwidth > 0 else 0.0
    if max(t_compute, t_memory) < latency_floor_s:
        return "latency"
    if bytes_accessed <= 0:
        return "compute"
    intensity = flops / bytes_accessed
    ridge = peak / bandwidth
    return "compute" if intensity >= ridge else "memory"


def fix_tag(op: OpCost, bound: str) -> str:
    """ROADMAP item-1 candidate that would move this op, or the honest
    alternatives: memory-layout work, or none (already at the roofline)."""
    hint = f"{op.source} {op.name} {' '.join(op.fusion_ops)}".lower()
    if op.opcode in _COLLECTIVES:
        return "comms-overlap"
    # the attention group announces itself three ways in real HLO: source
    # annotations ("...attn/..." modules, "attention" paths), softmax
    # fusions, and the bhqk einsum contraction names dot_product_attention
    # lowers to — all of them belong to the one fused-kernel fix
    if ("attention" in hint or "softmax" in hint or "attn" in hint
            or "bhqk" in hint):
        return "pallas-attention"
    if bound == "compute" and (
            op.opcode in ("dot", "convolution")
            or "dot" in op.fusion_ops or "convolution" in op.fusion_ops):
        return "fp8-matmul"
    if bound == "memory":
        return "memory-layout"
    if bound == "latency":
        return "none-latency"
    return "none-at-roofline"


def fix_registry() -> dict:
    """The in-tree kernel registry keyed by fix tag (ops/pallas), or an
    empty dict if the kernel package can't import on this host — the
    report then degrades to tags-only, never errors."""
    try:
        from distkeras_tpu.ops.pallas import kernel_registry

        return kernel_registry()
    except Exception:
        return {}


@dataclass
class RooflineRow:
    op: str           # grouped display name (source annotation or opcode)
    opcode: str
    bound: str        # memory | compute | latency
    flops: float
    bytes_accessed: float
    intensity: Optional[float]
    est_time_s: float
    headroom_s: float  # time above the pure-compute roofline
    share: float       # est_time_s / report total
    fix: str
    count: int = 1
    measured: bool = False  # est_time_s from a profiler trace
    #: an in-tree kernel implements this fix tag but its ablation flag is
    #: OFF — flipping one flag (after its kernel_ablate.py gate passes on
    #: real hardware) would act on this op. False both when no kernel
    #: exists AND when the kernel is already enabled (nothing to flip).
    fix_available: bool = False

    def to_row(self) -> dict:
        return {"kind": "op", "op": self.op, "opcode": self.opcode,
                "bound": self.bound, "flops": self.flops,
                "bytes": self.bytes_accessed,
                "intensity": (None if self.intensity is None
                              else round(self.intensity, 3)),
                "est_time_s": self.est_time_s,
                "headroom_s": self.headroom_s,
                "share": round(self.share, 4), "fix": self.fix,
                "count": self.count, "measured": self.measured,
                "fix_available": self.fix_available}


@dataclass
class RooflineReport:
    rows: List[RooflineRow] = field(default_factory=list)  # ALL grouped ops
    available: bool = True
    note: str = ""
    dtype: str = "bf16"
    peak_flops: float = 0.0
    hbm_bandwidth: float = 0.0
    top_k: int = 8
    total_time_s: float = 0.0
    coverage: Optional[float] = None   # inventory flops / modeled flops
    measured_share: float = 0.0        # time fraction backed by a trace
    while_floor: bool = False

    @property
    def ridge(self) -> float:
        """Ridge point, FLOPs/byte: intensity where compute takes over."""
        if self.hbm_bandwidth <= 0:
            return 0.0
        return self.peak_flops / self.hbm_bandwidth

    def top(self) -> List[RooflineRow]:
        """Top-k by time-weighted headroom (then by time): the ops where a
        fix buys the most wall-clock back."""
        ranked = sorted(self.rows, key=lambda r: (-r.headroom_s,
                                                  -r.est_time_s, r.op))
        return ranked[:self.top_k]

    def digest(self) -> dict:
        """Small deterministic dict for the health status digest and the
        flight-recorder postmortem bundle."""
        out = {"dtype": self.dtype, "available": self.available}
        if not self.available:
            out["note"] = self.note
            return out
        if self.coverage is not None:
            out["coverage"] = round(self.coverage, 3)
        out["top"] = [{"op": r.op, "bound": r.bound,
                       "share": round(r.share, 4), "fix": r.fix,
                       "fix_available": r.fix_available}
                      for r in self.top()[:3]]
        return out

    def publish(self) -> None:
        """Gauges for the health plane (``profile.op.share`` per top op,
        ``profile.op.coverage``) plus the digest stamped onto the flight
        recorder, if one is installed (recorder stays jax-free — it only
        ever sees this plain dict)."""
        if self.available:
            for r in self.top():
                telemetry.gauge("profile.op.share", op=r.op.replace(
                    ",", ";"), bound=r.bound).set(r.share)
            if self.coverage is not None:
                telemetry.gauge("profile.op.coverage").set(self.coverage)
        rec = telemetry.get_recorder()
        if rec is not None and hasattr(rec, "set_roofline"):
            rec.set_roofline(self.digest())

    def render(self) -> str:
        """Fixed-width table, biggest headroom first."""
        if not self.available:
            return f"roofline: no cost model on this backend ({self.note})"
        lines = [
            f"roofline vs {self.dtype} peak {self.peak_flops/1e12:.1f} "
            f"TFLOP/s, HBM {self.hbm_bandwidth/1e9:.0f} GB/s "
            f"(ridge {self.ridge:.1f} FLOP/B)"
            + (f", coverage {self.coverage:.1%}"
               if self.coverage is not None else "")
            + (" [while counted once: floor]" if self.while_floor else ""),
            f"{'op':<38}{'bound':>8}{'share':>7}{'AI':>9}"
            f"{'GFLOP':>9}{'MB':>9}  fix",
        ]
        for r in self.top():
            ai = "-" if r.intensity is None else f"{r.intensity:.1f}"
            src = "*" if r.measured else " "
            avail = " [kernel in-tree, off]" if r.fix_available else ""
            lines.append(
                f"{r.op[:37]:<38}{r.bound:>8}{r.share:>6.1%}{ai:>9}"
                f"{r.flops/1e9:>9.2f}{r.bytes_accessed/1e6:>9.2f}"
                f" {src}{r.fix}{avail}")
        lines.append("(* = measured time from a profiler trace; others "
                     "modeled — XLA-style shape arithmetic, not DMA "
                     "counters; [kernel in-tree, off] = a pallas kernel "
                     "implements this fix but its ablation flag is off)")
        return "\n".join(lines)


def build_report(inventory: OpInventory,
                 dtype: str = "bf16",
                 peak_flops: Optional[float] = None,
                 hbm_bandwidth: Optional[float] = None,
                 device=None,
                 measured: Optional[Dict[str, float]] = None,
                 modeled_flops: Optional[float] = None,
                 top_k: int = 8) -> RooflineReport:
    """Classify an op inventory against one chip's ceilings.

    ``peak_flops``/``hbm_bandwidth`` default to the local device's table
    entries; on hosts without either (CPU) the caller must supply explicit
    reference ceilings or the report declines (``available=False``) rather
    than classifying against invented numbers. ``measured`` maps HLO
    instruction names to profiled seconds (``profiling.scopes.join(xplane,
    tables).instructions[(kind, key)]``: the exact join of a profiler
    trace with that executable's table, seconds summed over the traced
    runs); matching rows rank by measured time, the rest by modeled time. ``modeled_flops`` is
    the analytic compute-phase total (``observability.count_flops``) the
    coverage fraction is taken against.
    """
    if not inventory.available:
        return RooflineReport(available=False, note=inventory.note,
                              dtype=dtype, top_k=top_k)
    if peak_flops is None:
        peak_flops = observability.device_peak_flops(device, dtype=dtype)
    if hbm_bandwidth is None:
        hbm_bandwidth = observability.device_hbm_bandwidth(device)
    if not peak_flops or not hbm_bandwidth:
        return RooflineReport(
            available=False, dtype=dtype, top_k=top_k,
            note="no peak/bandwidth table entry for this device; pass "
                 "explicit reference ceilings")
    measured = measured or {}

    # group raw rows by (opcode, source), joining measured times first so
    # a grouped row's time is the sum of its members' times.
    groups: Dict[tuple, dict] = {}
    for r in inventory.rows:
        key = (r.opcode, r.source)
        g = groups.setdefault(key, {
            "op": r.source or r.name, "opcode": r.opcode, "flops": 0.0,
            "bytes": 0.0, "count": 0, "measured_s": 0.0, "modeled_s": 0.0,
            "proto": r})
        g["flops"] += r.flops
        g["bytes"] += r.bytes_accessed
        g["count"] += 1
        t_model = max(r.flops / peak_flops,
                      r.bytes_accessed / hbm_bandwidth, LATENCY_FLOOR_S)
        if r.name in measured:
            g["measured_s"] += measured[r.name]
        else:
            g["modeled_s"] += t_model

    rows: List[RooflineRow] = []
    total_t = measured_t = 0.0
    for g in groups.values():
        est = g["measured_s"] + g["modeled_s"]
        total_t += est
        measured_t += g["measured_s"]
    total_t = total_t or 1.0
    registry = fix_registry()
    for key in sorted(groups):
        g = groups[key]
        est = g["measured_s"] + g["modeled_s"]
        bound = classify(g["flops"], g["bytes"], peak_flops, hbm_bandwidth)
        intensity = (g["flops"] / g["bytes"]) if g["bytes"] > 0 else None
        headroom = max(0.0, est - g["flops"] / peak_flops)
        fix = fix_tag(g["proto"], bound)
        kernel = registry.get(fix)
        rows.append(RooflineRow(
            op=g["op"], opcode=g["opcode"], bound=bound,
            flops=g["flops"], bytes_accessed=g["bytes"],
            intensity=intensity, est_time_s=est, headroom_s=headroom,
            share=est / total_t, fix=fix,
            count=g["count"], measured=g["measured_s"] > 0,
            fix_available=bool(kernel) and not kernel["enabled"]))

    coverage = None
    if modeled_flops:
        coverage = inventory.total_flops / modeled_flops
    return RooflineReport(
        rows=rows, dtype=dtype, peak_flops=peak_flops,
        hbm_bandwidth=hbm_bandwidth, top_k=top_k, total_time_s=total_t,
        coverage=coverage, measured_share=measured_t / total_t,
        while_floor=inventory.while_floor)
