"""Reduction from a profiler trace to numbers: which seconds an operation
ran on each device, the idle gaps between them and what the host was doing
in each, time per operation and per executable, and the part of the
collective operations' time during which nothing else ran.

Two halves. :func:`load` reads an ``.xplane.pb`` with
``jax.profiler.ProfileData`` into plain lists of ``(name, start_s, dur_s)``
events per device and for the host. Everything below it is arithmetic over
such lists, checked by ``perf/selftest.py`` on hand-made events.

What a device plane looks like on the TPU of this installation (looked at
by hand, PERF.md section 6): planes named ``/device:TPU:<n>``; the line
``XLA Ops`` holds one event per executed HLO operation, back to back on
the core; ``Async XLA Ops`` the asynchronous copies and collectives that
run beside them (``*-start`` to ``*-done``); ``XLA Modules`` one event per
executable run (``jit_<function name>(<fingerprint>)``); ``Steps`` the
profiler's own grouping. The host is ``/host:CPU``, one line a thread; its
``python`` line holds ``PjitFunction(..)`` and ``np.asarray(jax.Array)``.
"""

from __future__ import annotations

import functools
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_s, dur_s
Interval = Tuple[float, float]            # start_s, end_s

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVE_MARKS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all", "allreduce")
#: idle gaps shorter than this are launch latency between two operations of
#: one executable, not something the host could fill
MIN_GAP_S = 20e-6
#: how many of the longest gaps get a label from the host's events
LABELLED_GAPS = 400


def find_xplane(logdir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path: str) -> dict:
    """``{"devices": {plane: {"ops": [Event], "modules": [Event],
    "lines": {line: count}}}, "host": [Event]}``, seconds from the
    profile's own zero."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = {"ops": [], "modules": [], "async": [], "lines": {}}
            for line in plane.lines:
                events = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                          for e in line.events]
                dev["lines"][line.name] = len(events)
                if line.name == OPS_LINE:
                    dev["ops"].extend(events)
                elif line.name == MODULES_LINE:
                    dev["modules"].extend(events)
                elif line.name == ASYNC_LINE:
                    dev["async"].extend(events)
            devices[plane.name] = dev
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                            for e in line.events if e.duration_ns > 0)
    return {"devices": devices, "host": host}


# -- interval arithmetic -----------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted disjoint intervals covering the same points."""
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def length(disjoint: Sequence[Interval]) -> float:
    return sum(hi - lo for lo, hi in disjoint)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Points of disjoint sorted ``a`` not in disjoint sorted ``b``."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def spans_of(events: Iterable[Event]) -> List[Interval]:
    return [(s, s + d) for _, s, d in events]


def busy_intervals(ops: Iterable[Event]) -> List[Interval]:
    """Union of the intervals in which an operation ran."""
    return union(spans_of(ops))


def idle_gaps(ops: Iterable[Event], lo: float, hi: float) -> List[Interval]:
    """The parts of ``[lo, hi]`` in which no operation ran."""
    return subtract([(lo, hi)], busy_intervals(ops))


def is_collective(name: str) -> bool:
    low = name.lower()
    return any(mark in low for mark in COLLECTIVE_MARKS)


def exposed_collective_seconds(ops: Iterable[Event]) -> float:
    """Seconds inside collective operations during which no other operation
    ran on the same device."""
    ops = list(ops)
    coll = union(spans_of(e for e in ops if is_collective(e[0])))
    other = union(spans_of(e for e in ops if not is_collective(e[0])))
    return length(subtract(coll, other))


#: operations that only contain others (their events span their bodies')
CONTAINERS = ("while", "conditional", "call")


@functools.lru_cache(maxsize=None)
def short_name(full: str) -> str:
    """``%copy.564 = bf16[33,1024,16,64]{3,2,1,0:T(8,128)} copy(...)`` ->
    ``copy bf16[33,1024,16,64]``: the instruction's name without its number
    and its (first) output shape without the layout, so that the same
    operation in every layer sums under one name."""
    head, _, rest = full.partition(" = ")
    base = re.sub(r"\.\d+$", "", head.strip().lstrip("%"))
    if not rest:
        return base[:80]
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    more = ", .." if rest.startswith("(") else ""
    return f"{base} {shape.group(1) if shape else ''}{more}".strip()[:80]


def leaf_ops(ops: Iterable[Event]) -> List[Event]:
    """The operations that do work themselves, under their short names."""
    out = []
    for name, start, dur in ops:
        short = short_name(name)
        if short.split(" ", 1)[0] not in CONTAINERS:
            out.append((short, start, dur))
    return out


def sum_by_name(events: Iterable[Event]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for name, _, dur in events:
        out[name] = out.get(name, 0.0) + dur
    return out


def top(sums: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(sums.items(),
                                      key=lambda kv: -kv[1])[:n]]


def module_key(name: str) -> str:
    """``jit_decode(1234567)`` -> ``jit_decode``: runs of one jitted
    function under one name, whatever the fingerprint."""
    return name.split("(", 1)[0]


def module_stats(modules: Iterable[Event]) -> Dict[str, dict]:
    """Per executable: runs and device seconds."""
    out: Dict[str, dict] = {}
    for name, _, dur in modules:
        row = out.setdefault(module_key(name), {"runs": 0, "seconds": 0.0})
        row["runs"] += 1
        row["seconds"] += dur
    return out


def whole_runs(modules: Iterable[Event], lo: float, hi: float,
               edge_s: float = 1e-3) -> List[Event]:
    """The runs that lie wholly inside ``[lo, hi]``, not cut by its edges."""
    return [e for e in modules
            if e[1] > lo + edge_s and e[1] + e[2] < hi - edge_s]


def label_gaps(gaps: Sequence[Interval], host: Sequence[Event],
               fallback: str = "unattributed") -> Dict[str, float]:
    """Idle seconds by what the host was doing. Each of the longest gaps
    takes the name of the host event that overlaps it most (of several that
    cover it whole, the shortest, which is the most specific); the rest
    are summed as ``short gaps``."""
    import bisect

    ranked = sorted(gaps, key=lambda g: g[0] - g[1])
    hosts = sorted(host, key=lambda e: e[1])
    starts = [e[1] for e in hosts]
    longest = max((e[2] for e in hosts), default=0.0)
    out: Dict[str, float] = {}
    for lo, hi in ranked[:LABELLED_GAPS]:
        best, best_key = fallback, (0.0, 0.0)
        i = bisect.bisect_left(starts, lo - longest)
        j = bisect.bisect_right(starts, hi)
        for name, s, d in hosts[i:j]:
            overlap = min(hi, s + d) - max(lo, s)
            if overlap > 0 and (overlap, -d) > best_key:
                best, best_key = name, (overlap, -d)
        out[best] = out.get(best, 0.0) + (hi - lo)
    rest = sum(hi - lo for lo, hi in ranked[LABELLED_GAPS:])
    if rest > 0:
        out["short gaps"] = out.get("short gaps", 0.0) + rest
    return out


def reduce(trace: dict, window_s: float, used_devices: Optional[int] = None
           ) -> dict:
    """Everything the harness and the per-layer readers take from a trace.

    ``window_s`` is the traced window on the host's clock (from the return
    of ``start_trace`` to the call of ``stop_trace``). Busy seconds are
    averaged over the devices that ran anything; gaps and their labels are
    those of the busiest-named first device, inside the span of its own
    first and last operation."""
    devices = {k: v for k, v in sorted(trace["devices"].items())
               if v["ops"]}
    if used_devices:
        devices = dict(list(devices.items())[:used_devices])
    if not devices:
        return {"devices": 0}
    busy = {k: length(busy_intervals(v["ops"])) for k, v in devices.items()}
    first = next(iter(devices.values()))
    lo = min(s for _, s, _ in first["ops"])
    hi = max(s + d for _, s, d in first["ops"])
    gaps = [g for g in idle_gaps(first["ops"], lo, hi)
            if g[1] - g[0] >= MIN_GAP_S]
    op_sums: Dict[str, float] = {}
    modules: Dict[str, dict] = {}
    for v in devices.values():
        for k, s in sum_by_name(leaf_ops(v["ops"])).items():
            op_sums[k] = op_sums.get(k, 0.0) + s / len(devices)
        for k, row in module_stats(v["modules"]).items():
            agg = modules.setdefault(k, {"runs": 0, "seconds": 0.0})
            agg["runs"] += row["runs"] / len(devices)
            agg["seconds"] += row["seconds"] / len(devices)
    whole = whole_runs(first["modules"], lo, hi)
    # the instruction that took most time runs once per optimizer step (or
    # per decode step): its count says how many steps the trace holds, also
    # where no run of the executable lies whole inside it
    by_instruction: Dict[str, list] = {}
    for name, _, dur in first["ops"]:
        if short_name(name).split(" ", 1)[0] not in CONTAINERS:
            row = by_instruction.setdefault(name, [0, 0.0])
            row[0] += 1
            row[1] += dur
    marker = max(by_instruction.values(), key=lambda r: r[1], default=[0, 0])
    return {
        "marker_runs": marker[0],
        "busy_s_first_device": busy[next(iter(devices))],
        "devices": len(devices),
        "window_s": window_s,
        "busy_s": sum(busy.values()) / len(busy),
        "busy_s_by_device": busy,
        "span_s": hi - lo,
        "op_seconds": op_sums,
        "modules": modules,
        "whole_runs": module_stats(whole),
        "exposed_collective_s": sum(
            exposed_collective_seconds(v["ops"]) for v in devices.values())
        / len(devices),
        "collective_s": sum(
            length(union(spans_of(
                e for e in v["ops"] + v.get("async", [])
                if is_collective(e[0])))) for v in devices.values())
        / len(devices),
        "idle_by_host_activity": label_gaps(gaps, trace["host"]),
        "lines": {k: v["lines"] for k, v in devices.items()},
    }


def breakdown(reduced: dict) -> dict:
    """The ``breakdown`` of a traced run's last line."""
    return {"device_ops": top(reduced.get("op_seconds", {})),
            "idle_gaps": top(reduced.get("idle_by_host_activity", {}))}


def _look(path: str) -> None:
    """Print what a trace file holds, for a look by hand: planes, lines,
    event counts, a few names, and the host events that took most time."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            names = sum_by_name((e.name, 0.0, e.duration_ns * 1e-9)
                                for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{sum(names.values()):.4f} s; top: "
                  f"{[(k[:60], round(v, 4)) for k, v in top(names, 6)]}")


if __name__ == "__main__":
    import sys

    _look(sys.argv[1])
