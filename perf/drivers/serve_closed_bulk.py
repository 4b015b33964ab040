"""Driver of the closed-loop serving cells of a configuration whose layers
send each token to a few of many experts: ``serve_closed``'s load, window
and metrics as they are, and a check that holds two limits over a larger
sample.

``perf/serving.py`` judges the largest gap among the tokens of 8 sampled
requests (``tolerance.serve_logit_gap``). Where a router picks the k
largest gates, a token whose k-th and next gates nearly tie goes to another
expert when its input differs in the last bit kept, so the largest gap is
such a token in the precision the configuration states and in the one below
it alike: that limit catches a stale row, a wrong position or mask, and
cannot tell a bfloat16 router, softmax or norm from a float32 one. Rounding
moves every token a little, so this driver also holds the bulk:
``tolerance.serve_gap_mean = {"cap": c, "limit": m}``, the mean over the
checked tokens of ``min(gap, c)``, at most ``m``. The cap keeps a few
flipped tokens from deciding it. Some 5 % of the tokens have a gap at all,
so 8 requests (~1100 tokens, ~50 of them) leave that mean a noise of 17 %
and the two precisions 2 of its standard deviations apart; the mix's
``check_sample`` requests (64: ~8900 tokens) leave them 5 to 6 apart on
either side of the limit (PERF.md section 6).

``perf/serving.py`` has the sample's size as a constant and hands on
neither the gaps nor the weights, and this PR may not edit it: for its run
this driver sets the one and puts :func:`sampled_gaps` in the place of
``serving._check``, which computes the same gaps once and keeps them. A
``benchmark`` PR that gives ``serving._check`` the second limit and the
mix's sample removes this file (PERF.md section 7).
"""

import json
import os
from unittest import mock

import numpy as np

import harness
import serving
from drivers import serve_closed

REPORTS = serve_closed.REPORTS


def sampled_gaps(cfg: dict, reference, params, records, chooser=None):
    """The gaps ``serving._check`` takes the largest of, every one of them:
    for each sampled finished request, each emitted token's reference logit
    under its position's maximum, in one full forward over prompt plus
    answer padded to the context length. With ``chooser(params, ids)`` the
    tokens it would have emitted after the same context stand in for the
    emitted ones (``perf/precision_control.py``)."""
    import jax

    t_max = cfg["n_positions"]
    gaps_fn = jax.jit(lambda p, ids, chosen: reference.token_gaps(
        p, ids, cfg, chosen=chosen))
    out = []
    for r in records:
        if "prompt" not in r:
            continue
        n, tokens = len(r["prompt"]), r["tokens"][:t_max - len(r["prompt"])]
        ids = np.zeros(t_max, np.int32)
        ids[:n] = r["prompt"]
        ids[n:n + len(tokens)] = tokens
        chosen = ids[1:] if chooser is None else chooser(params, ids)
        out.append(np.asarray(gaps_fn(params, ids, chosen))
                   [n - 1:n - 1 + len(tokens)])
    return np.concatenate(out) if out else np.zeros(0, np.float32)


def judge(gaps: np.ndarray, tolerance: dict) -> dict:
    """Both limits on one set of gaps: what a run logs, and ``ok``."""
    bulk = tolerance["serve_gap_mean"]
    if gaps.size == 0 or not np.isfinite(gaps).all():
        return {"tokens": int(gaps.size), "ok": False}
    worst = float(gaps.max())
    mean = float(np.minimum(gaps, bulk["cap"]).mean())
    return {"tokens": int(gaps.size), "exact": int((gaps == 0).sum()),
            "worst": worst, "capped_mean": mean,
            "ok": worst <= tolerance["serve_logit_gap"]
            and mean <= bulk["limit"]}


def gaps_path(workload: str) -> str:
    """Where a run leaves the gaps it judged, for the control to read."""
    return os.path.join(harness.OUT_DIR, f"{workload}.gaps.json")


def run(ctx) -> dict:
    cfg = ctx.config
    kept = []

    def check(ctx, params, records):      # serving._check's contract
        gaps = sampled_gaps(cfg, ctx.reference, params, records)
        kept.append(gaps)
        return (float(gaps.max()) if gaps.size else 0.0,
                int((gaps == 0).sum()), int(gaps.size))

    with mock.patch.multiple(
            serving, CHECK_SAMPLE=int(ctx.traffic["check_sample"]),
            _check=check):
        result = serve_closed.run(ctx)
    verdict = judge(kept[0], cfg["tolerance"])
    bulk = cfg["tolerance"]["serve_gap_mean"]
    ctx.log(f"reference, the bulk: mean of min(gap, {bulk['cap']}) over "
            f"those {verdict['tokens']} tokens = "
            f"{verdict.get('capped_mean')} (tolerance {bulk['limit']})")
    with open(gaps_path(ctx.workload), "w") as f:
        json.dump([float(g) for g in kept[0]], f)
    result["correct"] = bool(result["correct"] and verdict["ok"])
    return result
