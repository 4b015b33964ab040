"""Metric arithmetic of the benchmark: percentiles, spreads, and the
reduction from the load generator's per-request records to what a client
felt. Pure Python, no JAX, so that the load generator's child process and
``perf/selftest.py`` share it.

Clocks: every time is a ``time.perf_counter()`` reading (CLOCK_MONOTONIC on
Linux, one epoch for every process of the machine), taken in the process
that saw the event.
"""

from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence

#: token arrivals closer than this belong to one stream frame: the client
#: loops over a frame's tokens in microseconds, while two frames are at
#: least a decode step (milliseconds) apart. Such zero gaps are dropped.
SAME_FRAME_S = 2e-4


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (``q`` in 0..100): the smallest value with at
    least ``q`` percent of the sample at or below it. None for no sample."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return float(ordered[rank - 1])


def median(values: Sequence[float]) -> Optional[float]:
    """The usual median (mean of the two middle values of an even sample)."""
    if not values:
        return None
    ordered = sorted(values)
    mid = len(ordered) // 2
    if len(ordered) % 2:
        return float(ordered[mid])
    return (ordered[mid - 1] + ordered[mid]) / 2.0


def quartile_spread(values: Sequence[float]) -> Optional[float]:
    """Distance between the quartiles over the median: the spread the
    driver reads from a set of runs (linear interpolation between ranks)."""
    if len(values) < 2:
        return None
    ordered = sorted(values)

    def at(q: float) -> float:
        pos = q * (len(ordered) - 1)
        lo = int(math.floor(pos))
        hi = min(lo + 1, len(ordered) - 1)
        return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)

    return (at(0.75) - at(0.25)) / abs(at(0.5))


def due_in(records: Iterable[dict], t0: float, t1: float) -> List[dict]:
    """Requests whose due time falls inside the window."""
    return [r for r in records if t0 <= r["due"] < t1]


def ttft_values(records: Iterable[dict], t0: float, t1: float,
                limit_s: float) -> List[float]:
    """Time from when each request was *due* to its first token, for the
    requests due in the window. A request that failed, was refused or never
    got a token counts as the drain limit: it missed any latency limit."""
    out = []
    for r in due_in(records, t0, t1):
        if r.get("error") is None and r["token_times"]:
            out.append(min(r["token_times"][0] - r["due"], limit_s))
        else:
            out.append(limit_s)
    return out


def gap_values(records: Iterable[dict], t0: float, t1: float) -> List[float]:
    """Gaps between consecutive token arrivals of the requests due in the
    window. Tokens of one stream frame share an arrival; zero gaps drop."""
    out = []
    for r in due_in(records, t0, t1):
        times = r["token_times"]
        out.extend(b - a for a, b in zip(times, times[1:])
                   if b - a > SAME_FRAME_S)
    return out


def tokens_between(records: Iterable[dict], t0: float, t1: float) -> int:
    """Output tokens whose arrival falls inside the window."""
    return sum(1 for r in records for t in r["token_times"] if t0 <= t < t1)


def context_positions_between(records: Iterable[dict], t0: float,
                              t1: float) -> int:
    """Sum, over the tokens that arrived in the window, of the context each
    was decoded against (prompt plus the tokens before it): the cached
    positions a decode step really needed, summed over lanes and steps."""
    total = 0
    for r in records:
        for i, t in enumerate(r["token_times"]):
            if i > 0 and t0 <= t < t1:    # token 0 comes from the prefill
                total += r["prompt_len"] + i
    return total
