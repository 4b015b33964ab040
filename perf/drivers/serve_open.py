"""Driver of the open-loop serving cells: independent users. Requests are
sent on a schedule drawn from the seed (``arrivals`` of the traffic mix)
whether or not earlier ones have finished, at a rate fixed in the cell, and
each is timed from when it was *due*. Below the knee the tails are what
users feel: the 95th percentiles of time to first token and of the gap
between tokens, over the requests due in the window.
"""

#: the end-to-end metrics a cell of this driver reports
REPORTS = ("serve_ttft_p95_s", "serve_gap_p95_s", "setup_s")

import serving
import stats


def run(ctx) -> dict:
    records = serving.serve(ctx, "open")
    t0, t1 = ctx.facts["window_rel"]
    ttft = stats.ttft_values(records, t0, t1, ctx.facts["drain_s"])
    gaps = stats.gap_values(records, t0, t1)
    attempted, failed, wrong = serving.window_counts(ctx, records)
    ctx.end_to_end = {
        "serve_ttft_p95_s": {"value": stats.percentile(ttft, 95),
                             "unit": "s"},
        "serve_gap_p95_s": {"value": stats.percentile(gaps, 95), "unit": "s"},
        "setup_s": {"value": ctx.window[0] - ctx.t_process_start,
                    "unit": "s"}}
    ctx.log(f"window: {attempted} requests due ({attempted / ctx.seconds:.2f}"
            f"/s), {failed} failed, {wrong} of wrong length; time to first "
            f"token p50 {stats.percentile(ttft, 50)}, p95 "
            f"{stats.percentile(ttft, 95)} over {len(ttft)}; gap p50 "
            f"{stats.percentile(gaps, 50)}, p95 {stats.percentile(gaps, 95)} "
            f"over {len(gaps)}; {stats.tokens_between(records, t0, t1)} "
            f"tokens arrived in the window")
    return {"correct": ctx.facts["check_ok"] and wrong == 0,
            "attempted": attempted, "failed": failed}
