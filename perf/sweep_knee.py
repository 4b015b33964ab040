"""The rate sweep that finds the knee of an open-loop cell, once: the
highest arrival rate the system sustains. Run on the chip when the cell is
defined; the table goes into PERF.md and four fifths of the knee into the
cell's file as a number. The benchmark itself never searches for a rate.

    python perf/sweep_knee.py --workload <open-loop cell> --rates 4,6,8,10 \\
        [--seconds 20] [--seed 0]

One engine, one phase of load per rate (each with a load generator of its
own, its ramp and its drain). A rate is *sustained* when nothing failed or
was refused, the backlog did not grow (the median time to first token of
the window's second half is no more than 1.5 times the first half's plus
50 ms) and the 95th percentile of time to first token stays under
``--ttft-limit`` seconds.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

import harness  # noqa: E402
import run as run_module  # noqa: E402
import serving  # noqa: E402
import stats  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ttft-limit", type=float, default=2.0)
    args = ap.parse_args()
    args.trace, args.keep_trace = 0, None
    cell = harness.load_cell(args.workload)
    device = harness.check_device(cell["chips"], cell["rehearsal"])
    os.makedirs(harness.OUT_DIR, exist_ok=True)
    harness.setup_compile_cache()
    ctx = run_module.Context(args, cell, device)
    stack = serving.Stack(ctx)
    rows = []
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            traffic = dict(ctx.traffic,
                           arrivals=dict(ctx.traffic["arrivals"], rate=rate))
            child = serving.spawn_loadgen()
            try:
                load = serving.run_load(ctx, stack, child, "open", traffic,
                                        args.seconds, f"sweep_{rate:g}")
            finally:
                if child.poll() is None:
                    child.kill()
                child.wait()
            recs = load["records"]
            t0, t1 = load["window_rel"]
            mid = (t0 + t1) / 2
            ttft = stats.ttft_values(recs, t0, t1, load["drain_s"])
            first = stats.ttft_values(recs, t0, mid, load["drain_s"])
            second = stats.ttft_values(recs, mid, t1, load["drain_s"])
            gaps = stats.gap_values(recs, t0, t1)
            due = stats.due_in(recs, t0, t1)
            failed = sum(1 for r in due if r["error"] is not None)
            row = {
                "rate": rate, "due": len(due), "failed": failed,
                "ttft_p50": stats.percentile(ttft, 50),
                "ttft_p95": stats.percentile(ttft, 95),
                "ttft_p50_first_half": stats.percentile(first, 50),
                "ttft_p50_second_half": stats.percentile(second, 50),
                "gap_p50": stats.percentile(gaps, 50),
                "gap_p95": stats.percentile(gaps, 95),
                "tokens_per_s": stats.tokens_between(recs, t0, t1)
                / args.seconds,
                "lanes_mean": load["decode_lanes_mean"],
                "step_s_p50": load["step_s_p50"]}
            row["sustained"] = bool(
                failed == 0 and row["ttft_p95"] < args.ttft_limit
                and row["ttft_p50_second_half"]
                <= 1.5 * row["ttft_p50_first_half"] + 0.05)
            rows.append(row)
            print("SWEEP " + json.dumps(row), flush=True)
    finally:
        stack.close()
    ok = [r["rate"] for r in rows if r["sustained"]]
    knee = max(ok) if ok else None
    print(json.dumps({
        "device": {k: device[k] for k in ("platform", "kind", "count")},
        "workload": args.workload, "seconds": args.seconds,
        "knee": knee, "cell_rate": None if knee is None else 0.8 * knee,
        "rehearsal": cell["rehearsal"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
