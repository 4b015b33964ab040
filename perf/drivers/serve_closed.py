"""Driver of the closed-loop serving cells: ``callers`` callers, each
sending its next request when the last completes (offline batch generation:
evaluation harnesses, data labelling). The queue is never empty, so what a
chip-hour buys is output tokens per second: tokens whose arrival at the
client falls inside the window, over the window and the chips. Load stops
when the window closes; ``attempted`` counts the requests that ended inside
it. Time to first token and gaps are recorded among the per-layer metrics,
not judged.
"""

#: the end-to-end metrics a cell of this driver reports
REPORTS = ("serve_tokens_per_s", "setup_s")

import serving
import stats


def run(ctx) -> dict:
    records = serving.serve(ctx, "closed")
    t0, t1 = ctx.facts["window_rel"]
    tokens = stats.tokens_between(records, t0, t1)
    attempted, failed, wrong = serving.window_counts(ctx, records, ended=True)
    ctx.end_to_end = {
        "serve_tokens_per_s": {
            "value": tokens / ctx.seconds / ctx.chips, "unit": "tokens/s"},
        "setup_s": {"value": ctx.window[0] - ctx.t_process_start,
                    "unit": "s"}}
    ttft = [r["token_times"][0] - r["due"]
            for r in stats.due_in(records, t0, t1) if r["token_times"]]
    gaps = stats.gap_values(records, t0, t1)
    ctx.log(f"window: {tokens} tokens arrived "
            f"({tokens / ctx.seconds:.1f}/s); {attempted} requests ended, "
            f"{failed} failed, {wrong} of wrong length; time to first token "
            f"p50 {stats.percentile(ttft, 50)}, gap p50 "
            f"{stats.percentile(gaps, 50)}, p95 {stats.percentile(gaps, 95)}")
    return {"correct": ctx.facts["check_ok"] and wrong == 0,
            "attempted": attempted, "failed": failed}
