"""Perf-regression sentinel: judge this PR's numbers against the repo's
own committed history (DESIGN.md §16).

The telemetry plane measures (telemetry.py), attributes (attribution.py)
and now judges (health/slo.py) the LIVE run — this tool closes the last
loop and judges runs ACROSS releases. Three independent checks, each
emitting machine-readable verdict rows:

history (``--check history``)
    Loads the ``BENCH_r*.json`` release ladder under ``--repo-dir`` (none
    is committed any more: the old one was taken on an earlier
    installation; the next ladder is the driver's ledger) and asks whether
    the headline metrics (MFU, samples/sec/chip) are still improving:
    the newest release must beat the release ``--lookback`` steps behind
    it by at least ``--min-improvement`` (relative). The r03→r05 MFU
    plateau (0.5431 → 0.5474, +0.79% over two releases) is exactly what
    this catches: individually each release "didn't regress", but the
    ladder stopped climbing.

fresh (``--check fresh --fresh run.json``)
    Compares one fresh benchmark result (same ``parsed`` shape bench.py
    prints) against the newest committed release, with a NOISE BAND
    estimated from the history itself: the median absolute relative
    step between consecutive releases, floored at ``--noise-floor``.
    A fresh value is a regression only when it falls below baseline by
    more than the band — same median-of-pairs philosophy as
    attribution.py's overhead estimator (medians kill outlier pairs).

phases (``--check phases --phases-baseline a.jsonl --phases-fresh b.jsonl``)
    Diffs the per-phase window decomposition of two attribution.py
    evidence files and names the ``profile.phase.*`` whose share of the
    window grew by more than ``--phase-budget`` (absolute frac) — "the
    regression is real AND it lives in commit, not compute".

roofline (``--check roofline``)
    Learns the op-level ladder from the committed
    ``results/pr*_attribution_ops.jsonl`` files (attribution.py --ops
    rows) and judges the newest one against absolute floors (op coverage
    >= 0.90 of the executable's modeled FLOPs; default-path overhead <=
    2%) and against the prior file: any op whose share of modeled step
    time GREW by more than ``--op-budget`` (absolute) fails — so a
    future kernel PR must show its target op shrinking, not just the
    wall clock moving. Ops present in only one file don't vote (XLA is
    free to rename fusions between releases). A file carrying an
    in-file A/B (``kind="op_baseline"`` rows, attribution.py
    --attention) additionally gets the ``profile.op.attention_share``
    verdict: the pallas-attention group's summed share must SHRINK
    from the XLA baseline leg to the kernel leg of the SAME file.

decode (``--check decode``)
    Learns the serving-decode ladder from the committed
    ``results/pr*_decode_bench.jsonl`` files (decode_bench.py rows) and
    judges the newest one twice: against ABSOLUTE floors the serving
    charter sets (continuous >= 3x naive; warm-prefix TTFT >= 2x
    lower than cold; speculation > 1.0x useful-tokens/s — the
    DESIGN.md §19 acceptance bars, held forever, not just at merge) and
    against the prior file that carries the same metric, with the same
    noise-band rule as ``fresh``. Older files that predate a metric
    simply don't vote on it — absence is not a regression.

fleet (``--check fleet``)
    Learns the routed-fleet ladder from the committed
    ``results/pr*_fleet_probe.jsonl`` files (fleet_probe.py rows) and
    judges the newest one against the DESIGN.md §22 acceptance bars,
    held forever: affinity routing strictly beats the seeded
    random-routing control leg, a mid-traffic replica kill loses zero
    requests (all token-exact), and the disaggregated KV handoff is
    token-identical to local prefill+decode — plus the same
    noise-banded comparison against the prior evidence file.

soak (``--check soak``)
    Learns the chaos-soak ladder from the committed
    ``results/pr*_soak.jsonl`` files (soak.py summary rows) and judges
    the newest one against the DESIGN.md §24 acceptance bars, held
    forever: the soak ran at least its wall-clock floor, killed every
    authority (trainer worker, PS coordinator, data coordinator, a
    serving replica) at least once, lost zero windows and zero data
    ranges, answered every request token-exact, kept model_version
    strictly monotone across every publish, and the injected HBM-leak
    drill was caught by the trend detector AND landed as a typed event
    in a postmortem bundle.

Verdicts are JSONL rows ``{"kind": "verdict", "check": ..., "metric":
..., "status": "pass"|"fail", ...}`` written to ``--out`` (and stdout);
the process exits 0 iff every verdict passed, so CI can gate on it::

    python benchmarks/regression_gate.py --check history
    python benchmarks/regression_gate.py --check fresh --fresh run.json
    python benchmarks/regression_gate.py --check phases \
        --phases-baseline results/pr10_attribution.jsonl \
        --phases-fresh fresh_attribution.jsonl
    python benchmarks/regression_gate.py --check decode
    python benchmarks/regression_gate.py --check roofline
    python benchmarks/regression_gate.py --check fleet
    python benchmarks/regression_gate.py --check soak
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: headline metrics judged by the history/fresh checks, in the key names
#: bench.py's ``parsed`` dict uses. ``value`` is samples/sec/chip.
HEADLINE_METRICS = ("mfu", "value")

#: a release ladder can legitimately flatten once near roofline — but the
#: repo's own SLO floor says mfu >= 0.50 is "good", and the ladder's
#: charter (ROADMAP) is to keep climbing until then. 1% over the lookback
#: window is deliberately modest.
DEFAULT_MIN_IMPROVEMENT = 0.01
DEFAULT_LOOKBACK = 2
#: never let a noise band collapse below this (history can be eerily
#: quiet when two releases didn't touch the hot path at all)
DEFAULT_NOISE_FLOOR = 0.005
DEFAULT_PHASE_BUDGET = 0.02

#: decode-bench row field -> gated metric name, keyed by the row's
#: ``mode``. All higher-is-better by construction (ratios over the
#: leg's own baseline, never raw wall clocks — CPU hosts are noisy).
DECODE_METRICS = {
    "continuous": (("tokens_per_s", "decode.tokens_per_s"),),
    "summary": (("speedup_vs_naive", "decode.speedup_vs_naive"),),
    "prefix": (("ttft_speedup", "decode.prefix.ttft_speedup"),),
    "speculative": (("speedup_vs_plain", "decode.spec.speedup_vs_plain"),),
    "longtail": (("hbm_ratio_rect_over_paged", "decode.paged.hbm_ratio"),),
    # long-context serving economics (ISSUE 20)
    "interference": (("p99_improvement",
                      "decode.chunk.interference_improvement"),),
    "kv_capacity": (("capacity_ratio", "decode.kv.capacity_ratio"),
                    ("err_within_bound", "decode.kv.err_within_bound")),
    "sampled": (("sampled_identity", "decode.spec.sampled_identity"),
                ("speedup_vs_plain", "decode.spec.sampled_speedup")),
}

#: absolute floors from the serving charter (ISSUE 9 / DESIGN.md §19
#: acceptance). A ladder entry below its floor fails even with no
#: history to compare against.
DECODE_FLOORS = {
    "decode.speedup_vs_naive": 3.0,
    "decode.prefix.ttft_speedup": 2.0,
    "decode.spec.speedup_vs_plain": 1.0,
    # long-context serving economics (ISSUE 20)
    "decode.chunk.interference_improvement": 2.0,
    "decode.kv.capacity_ratio": 1.8,
    "decode.kv.err_within_bound": 1.0,
    "decode.spec.sampled_identity": 1.0,
    "decode.spec.sampled_speedup": 1.0,
}

#: fleet-probe row field -> gated metric name, keyed by the row's leg
#: (or its ``kind`` for the summary row). The gate names deliberately
#: live in the probe's own ``fleet_probe.`` namespace: the router's
#: ``fleet.*`` telemetry names are live instruments, these are derived
#: cross-leg verdict inputs.
FLEET_METRICS = {
    "affinity": (("prefix_hit_rate", "fleet_probe.affinity_hit_rate"),),
    "summary": (
        ("affinity_advantage", "fleet_probe.affinity_advantage"),
        ("kill_success_rate", "fleet_probe.kill_success_rate"),
        ("handoff_token_identical",
         "fleet_probe.handoff_token_identical"),
    ),
}

#: absolute floors from the fleet charter (ISSUE 17 / DESIGN.md §22
#: acceptance, held forever): affinity routing strictly beats the
#: seeded random control, a mid-traffic replica kill loses NOTHING
#: (every request re-queues and lands token-exact), and the
#: disaggregated KV handoff is token-identical to local prefill+decode.
FLEET_FLOORS = {
    "fleet_probe.affinity_advantage": 0.01,
    "fleet_probe.kill_success_rate": 1.0,
    "fleet_probe.handoff_token_identical": 1.0,
}

#: soak summary-row field -> gated metric name. The gate names live in
#: the probe's own ``soak_probe.`` namespace: ``soak.*`` names are the
#: harness's live instruments (METRIC_NAMES), these are derived
#: end-of-run verdict inputs. All higher-is-better (booleans as 0/1).
SOAK_METRICS = {
    "summary": (
        ("seconds", "soak_probe.seconds"),
        ("authorities_killed", "soak_probe.authorities_killed"),
        ("zero_lost_windows", "soak_probe.zero_lost_windows"),
        ("request_success_rate", "soak_probe.request_success_rate"),
        ("version_monotone", "soak_probe.version_monotone"),
        ("leak_drill_caught", "soak_probe.leak_drill_caught"),
    ),
}

#: absolute floors from the soak charter (ISSUE 19 / DESIGN.md §24
#: acceptance, held forever): a >=120s budget actually spent, every
#: authority killed at least once, the three flywheel invariants intact,
#: and the HBM-leak forensic drill caught-and-bundled. Deliberately NOT
#: gated: cycle/window counts (pure host-speed artifacts) and
#: zero-trend-breaches (a breach during chaos is the observatory
#: working — the summary row records them for the reviewer instead).
SOAK_FLOORS = {
    "soak_probe.seconds": 120.0,
    "soak_probe.authorities_killed": 4.0,
    "soak_probe.zero_lost_windows": 1.0,
    "soak_probe.request_success_rate": 1.0,
    "soak_probe.version_monotone": 1.0,
    "soak_probe.leak_drill_caught": 1.0,
}


# -- history loading --------------------------------------------------------

def load_history(repo_dir: str = REPO) -> List[Tuple[int, dict]]:
    """``[(release_n, parsed_dict), ...]`` sorted by release, from the
    ``BENCH_r*.json`` files in ``repo_dir``. Entries without a ``parsed`` dict
    (failed bench runs) are skipped — absence is not a regression."""
    out = []
    for path in sorted(glob.glob(os.path.join(repo_dir, "BENCH_r*.json"))):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if m is None:
            continue
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        parsed = doc.get("parsed")
        if isinstance(parsed, dict):
            out.append((int(m.group(1)), parsed))
    out.sort(key=lambda t: t[0])
    return out


def noise_band(history: List[Tuple[int, dict]], metric: str,
               floor: float = DEFAULT_NOISE_FLOOR) -> float:
    """Median absolute relative step between consecutive releases — the
    history's own run-to-run noise estimate (median-of-pairs: one odd
    release can't inflate the band)."""
    steps = []
    for (_, a), (_, b) in zip(history, history[1:]):
        va, vb = a.get(metric), b.get(metric)
        if va and vb:
            steps.append(abs(vb - va) / abs(va))
    if not steps:
        return floor
    steps.sort()
    mid = len(steps) // 2
    med = steps[mid] if len(steps) % 2 else (steps[mid - 1] +
                                             steps[mid]) / 2.0
    return max(med, floor)


def load_decode_history(repo_dir: str = REPO) -> List[Tuple[int, dict]]:
    """``[(pr_n, metrics_dict), ...]`` sorted by PR, from the committed
    ``benchmarks/results/pr*_decode_bench.jsonl`` evidence files.
    Metrics are extracted per DECODE_METRICS; a file contributes only
    the metrics its rows carry (the pre-paging pr9 file has no prefix/
    spec legs, and that's fine — it just doesn't vote on them)."""
    out = []
    pattern = os.path.join(repo_dir, "benchmarks", "results",
                           "pr*_decode_bench.jsonl")
    for path in sorted(glob.glob(pattern)):
        m = re.search(r"pr(\d+)_decode_bench\.jsonl$", path)
        if m is None:
            continue
        metrics: dict = {}
        try:
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    row = json.loads(line)
                    for field, name in DECODE_METRICS.get(
                            row.get("mode"), ()):
                        if row.get(field) is not None:
                            metrics[name] = row[field]
        except (OSError, ValueError):
            continue
        if metrics:
            out.append((int(m.group(1)), metrics))
    out.sort(key=lambda t: t[0])
    return out


def load_fleet_history(repo_dir: str = REPO) -> List[Tuple[int, dict]]:
    """``[(pr_n, metrics_dict), ...]`` sorted by PR, from the committed
    ``benchmarks/results/pr*_fleet_probe.jsonl`` evidence files
    (fleet_probe.py rows). Metrics are extracted per FLEET_METRICS."""
    out = []
    pattern = os.path.join(repo_dir, "benchmarks", "results",
                           "pr*_fleet_probe.jsonl")
    for path in sorted(glob.glob(pattern)):
        m = re.search(r"pr(\d+)_fleet_probe\.jsonl$", path)
        if m is None:
            continue
        metrics: dict = {}
        try:
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    row = json.loads(line)
                    key = (row.get("leg") if row.get("kind") == "leg"
                           else row.get("kind"))
                    for field, name in FLEET_METRICS.get(key, ()):
                        if row.get(field) is not None:
                            metrics[name] = row[field]
        except (OSError, ValueError):
            continue
        if metrics:
            out.append((int(m.group(1)), metrics))
    out.sort(key=lambda t: t[0])
    return out


def load_soak_history(repo_dir: str = REPO) -> List[Tuple[int, dict]]:
    """``[(pr_n, metrics_dict), ...]`` sorted by PR, from the committed
    ``benchmarks/results/pr*_soak.jsonl`` evidence files (soak.py rows).
    Metrics are extracted per SOAK_METRICS (the summary row)."""
    out = []
    pattern = os.path.join(repo_dir, "benchmarks", "results",
                           "pr*_soak.jsonl")
    for path in sorted(glob.glob(pattern)):
        m = re.search(r"pr(\d+)_soak\.jsonl$", path)
        if m is None:
            continue
        metrics: dict = {}
        try:
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    row = json.loads(line)
                    for field, name in SOAK_METRICS.get(
                            row.get("kind"), ()):
                        if row.get(field) is not None:
                            metrics[name] = row[field]
        except (OSError, ValueError):
            continue
        if metrics:
            out.append((int(m.group(1)), metrics))
    out.sort(key=lambda t: t[0])
    return out


#: absolute floors for the op-level ladder (ISSUE 16 acceptance):
#: coverage of the executable's modeled FLOPs, and the default-path
#: overhead of the per-window MFU publication.
ROOFLINE_COVERAGE_FLOOR = 0.90
ROOFLINE_OVERHEAD_CEIL = 0.02
DEFAULT_OP_BUDGET = 0.05


def load_roofline_history(repo_dir: str = REPO) -> List[Tuple[int, dict]]:
    """``[(pr_n, doc), ...]`` sorted by PR from the committed
    ``results/pr*_attribution_ops.jsonl`` files. ``doc`` carries
    ``coverage``/``overhead_frac`` plus ``shares`` ({op: share}) and
    ``bounds`` ({op: boundedness}) from the top-k op rows. A file that
    also carries ``kind="op_baseline"`` rows (attribution --attention,
    PR 18) is a within-file A/B: the summed share of its
    ``pallas-attention``-tagged rows lands in
    ``attention_share_baseline`` (baseline leg) and ``attention_share``
    (variant leg) for ``judge_roofline``'s shrink verdict."""
    out = []
    pattern = os.path.join(repo_dir, "benchmarks", "results",
                           "pr*_attribution_ops.jsonl")
    for path in sorted(glob.glob(pattern)):
        m = re.search(r"pr(\d+)_attribution_ops\.jsonl$", path)
        if m is None:
            continue
        doc: dict = {"shares": {}, "bounds": {}}
        try:
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    row = json.loads(line)
                    if row.get("kind") == "roofline":
                        doc["coverage"] = row.get("coverage")
                    elif row.get("kind") == "overhead":
                        doc["overhead_frac"] = row.get("overhead_frac")
                    elif row.get("kind") == "op":
                        doc["shares"][row["op"]] = row.get("share", 0.0)
                        doc["bounds"][row["op"]] = row.get("bound", "?")
                        if row.get("fix") == "pallas-attention":
                            doc["attention_share"] = (
                                doc.get("attention_share", 0.0)
                                + (row.get("share") or 0.0))
                    elif row.get("kind") == "op_baseline":
                        if row.get("fix") == "pallas-attention":
                            doc["attention_share_baseline"] = (
                                doc.get("attention_share_baseline", 0.0)
                                + (row.get("share") or 0.0))
        except (OSError, ValueError):
            continue
        if doc["shares"] or "coverage" in doc:
            out.append((int(m.group(1)), doc))
    out.sort(key=lambda t: t[0])
    return out


def judge_roofline(history: List[Tuple[int, dict]],
                   coverage_floor: float = ROOFLINE_COVERAGE_FLOOR,
                   overhead_ceil: float = ROOFLINE_OVERHEAD_CEIL,
                   op_budget: float = DEFAULT_OP_BUDGET) -> List[dict]:
    """Op-ladder gate: newest evidence vs the absolute floors, and each
    shared top-op's time share vs the prior release."""
    if not history:
        return [{"kind": "verdict", "check": "roofline", "metric": "*",
                 "status": "fail",
                 "note": "no pr*_attribution_ops.jsonl evidence "
                         "committed (run attribution.py --ops --run)"}]
    n_new, newest = history[-1]
    verdicts = []
    cov = newest.get("coverage")
    if cov is not None:
        status = "pass" if cov >= coverage_floor else "fail"
        verdicts.append({
            "kind": "verdict", "check": "roofline",
            "metric": "profile.op.coverage", "release": n_new,
            "observed": cov, "floor": coverage_floor, "status": status,
            "note": (f"pr{n_new:02d} op rows cover {cov:.1%} of the "
                     f"executable's modeled FLOPs (floor "
                     f"{coverage_floor:.0%})")})
    over = newest.get("overhead_frac")
    if over is not None:
        status = "pass" if over <= overhead_ceil else "fail"
        verdicts.append({
            "kind": "verdict", "check": "roofline",
            "metric": "profile.op.default_path_overhead",
            "release": n_new, "observed": over, "ceiling": overhead_ceil,
            "status": status,
            "note": (f"pr{n_new:02d} default-path overhead "
                     f"{over:+.2%} (ceiling {overhead_ceil:.0%}, "
                     f"capture stays opt-in)")})
    att_base = newest.get("attention_share_baseline")
    att_new = newest.get("attention_share")
    if att_base is not None:
        # within-file A/B (PR 18): the attention group's share of modeled
        # step time must SHRINK when the fused kernel replaces the XLA
        # path — judged on the same file because the kernel substitution
        # and its XLA baseline were derived from one compiled executable
        status = ("pass" if att_new is not None and att_new < att_base
                  else "fail")
        verdicts.append({
            "kind": "verdict", "check": "roofline",
            "metric": "profile.op.attention_share", "release": n_new,
            "baseline": round(att_base, 4),
            "observed": None if att_new is None else round(att_new, 4),
            "status": status,
            "note": (f"pr{n_new:02d} attention group share "
                     f"{att_base:.1%} (XLA baseline) -> "
                     + (f"{att_new:.1%} (flash kernel-modeled); must "
                        f"shrink" if att_new is not None
                        else "no variant rows"))})
    if len(history) >= 2:
        n_base, base = history[-2]
        shared = sorted(set(base["shares"]) & set(newest["shares"]))
        for op in shared:
            sb, sn = base["shares"][op], newest["shares"][op]
            shift = sn - sb
            status = "pass" if shift <= op_budget else "fail"
            verdicts.append({
                "kind": "verdict", "check": "roofline",
                "metric": f"profile.op.share{{op={op}}}",
                "baseline_release": n_base, "release": n_new,
                "baseline": sb, "observed": sn,
                "delta_frac": round(shift, 6), "budget_frac": op_budget,
                "bound": newest["bounds"].get(op, "?"),
                "status": status,
                "note": (f"pr{n_base:02d}->pr{n_new:02d} {op} step-time "
                         f"share {sb:.1%} -> {sn:.1%} ({shift:+.2%} vs "
                         f"{op_budget:.0%} budget, "
                         f"{newest['bounds'].get(op, '?')}-bound)")})
        if not shared:
            verdicts.append({
                "kind": "verdict", "check": "roofline",
                "metric": "profile.op.share", "status": "pass",
                "note": (f"pr{n_base:02d} and pr{n_new:02d} share no op "
                         f"names (XLA renamed fusions?); floors judged, "
                         f"drift not comparable")})
    if not verdicts:
        verdicts.append({"kind": "verdict", "check": "roofline",
                         "metric": "*", "status": "fail",
                         "note": "evidence files carry no gated values"})
    return verdicts


# -- checks -----------------------------------------------------------------

def judge_history(history: List[Tuple[int, dict]],
                  metrics=HEADLINE_METRICS,
                  lookback: int = DEFAULT_LOOKBACK,
                  min_improvement: float = DEFAULT_MIN_IMPROVEMENT
                  ) -> List[dict]:
    """Plateau detector: newest release vs the one ``lookback`` releases
    behind it must show ``min_improvement`` relative gain per metric."""
    verdicts = []
    if len(history) < lookback + 1:
        return [{"kind": "verdict", "check": "history", "metric": "*",
                 "status": "pass",
                 "note": f"only {len(history)} release(s); need "
                         f"{lookback + 1} for a plateau verdict"}]
    (n_old, old), (n_new, new) = history[-1 - lookback], history[-1]
    for metric in metrics:
        vo, vn = old.get(metric), new.get(metric)
        if not vo or vn is None:
            continue
        gain = (vn - vo) / abs(vo)
        status = "pass" if gain >= min_improvement else "fail"
        verdicts.append({
            "kind": "verdict", "check": "history", "metric": metric,
            "baseline_release": n_old, "release": n_new,
            "baseline": vo, "observed": vn,
            "delta_frac": round(gain, 6),
            "budget_frac": min_improvement, "status": status,
            "note": (f"r{n_old:02d}->r{n_new:02d} {metric} "
                     f"{vo} -> {vn} ({gain:+.2%}); "
                     + ("ladder still climbing" if status == "pass" else
                        f"plateau: below the {min_improvement:.0%} "
                        f"improvement budget over {lookback} release(s)")),
        })
    return verdicts


def judge_fresh(history: List[Tuple[int, dict]], fresh: dict,
                metrics=HEADLINE_METRICS,
                noise_floor: float = DEFAULT_NOISE_FLOOR) -> List[dict]:
    """Fresh-run gate: a metric fails only when it undercuts the newest
    committed release by more than the history's own noise band."""
    verdicts = []
    if not history:
        return [{"kind": "verdict", "check": "fresh", "metric": "*",
                 "status": "pass", "note": "no committed history"}]
    n_base, base = history[-1]
    for metric in metrics:
        vb, vf = base.get(metric), fresh.get(metric)
        if not vb or vf is None:
            continue
        band = noise_band(history, metric, floor=noise_floor)
        delta = (vf - vb) / abs(vb)
        status = "pass" if delta >= -band else "fail"
        verdicts.append({
            "kind": "verdict", "check": "fresh", "metric": metric,
            "baseline_release": n_base, "baseline": vb, "observed": vf,
            "delta_frac": round(delta, 6), "noise_band": round(band, 6),
            "status": status,
            "note": (f"fresh {metric} {vf} vs r{n_base:02d} {vb} "
                     f"({delta:+.2%}, noise band ±{band:.2%})"),
        })
    return verdicts


def _phase_fracs(jsonl_path: str) -> Dict[str, float]:
    """phase -> frac-of-window from an attribution.py evidence file (the
    ``decomposition`` row when present, else the ``phase`` rows)."""
    fracs: Dict[str, float] = {}
    with open(jsonl_path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            row = json.loads(line)
            if row.get("kind") == "decomposition":
                return {p: d.get("frac", 0.0)
                        for p, d in row.get("phases", {}).items()}
            if row.get("kind") == "phase":
                fracs[row["phase"]] = row.get("frac", 0.0)
    return fracs


def judge_phases(baseline_jsonl: str, fresh_jsonl: str,
                 budget_frac: float = DEFAULT_PHASE_BUDGET) -> List[dict]:
    """Name the phase that moved: any ``profile.phase.*`` whose share of
    the window grew by more than ``budget_frac`` (absolute) fails."""
    base, fresh = _phase_fracs(baseline_jsonl), _phase_fracs(fresh_jsonl)
    verdicts = []
    for phase in sorted(set(base) | set(fresh)):
        fb, ff = base.get(phase, 0.0), fresh.get(phase, 0.0)
        shift = ff - fb
        status = "pass" if shift <= budget_frac else "fail"
        verdicts.append({
            "kind": "verdict", "check": "phases",
            "metric": f"profile.phase.{phase}_s",
            "baseline": fb, "observed": ff,
            "delta_frac": round(shift, 6), "budget_frac": budget_frac,
            "status": status,
            "note": (f"{phase} window share {fb:.2%} -> {ff:.2%} "
                     f"({shift:+.2%} vs {budget_frac:.0%} budget)"),
        })
    if not verdicts:
        verdicts.append({"kind": "verdict", "check": "phases",
                         "metric": "*", "status": "fail",
                         "note": "no phase rows in either evidence file"})
    return verdicts


def _judge_ladder(check: str, history: List[Tuple[int, dict]],
                  floors: dict, noise_floor: float,
                  missing_note: str) -> List[dict]:
    """Shared per-PR evidence-ladder gate: the newest evidence file is
    judged against absolute charter floors AND against its own history
    (per-metric sub-ladder, noise-banded like ``fresh``)."""
    if not history:
        return [{"kind": "verdict", "check": check, "metric": "*",
                 "status": "fail", "note": missing_note}]
    n_new, newest = history[-1]
    verdicts = []
    for metric in sorted(newest):
        vn = newest[metric]
        floor = floors.get(metric)
        if floor is not None:
            status = "pass" if vn >= floor else "fail"
            verdicts.append({
                "kind": "verdict", "check": check, "metric": metric,
                "release": n_new, "observed": vn, "floor": floor,
                "status": status,
                "note": (f"pr{n_new:02d} {metric} {vn:.3f} vs charter "
                         f"floor {floor}")})
        sub = [(n, m) for n, m in history if metric in m]
        if len(sub) < 2:
            continue
        n_base, base = sub[-2]
        vb = base[metric]
        band = noise_band(sub, metric, floor=noise_floor)
        delta = (vn - vb) / abs(vb) if vb else vn - vb
        status = "pass" if delta >= -band else "fail"
        verdicts.append({
            "kind": "verdict", "check": check, "metric": metric,
            "baseline_release": n_base, "release": n_new,
            "baseline": vb, "observed": vn,
            "delta_frac": round(delta, 6), "noise_band": round(band, 6),
            "status": status,
            "note": (f"pr{n_base:02d}->pr{n_new:02d} {metric} "
                     f"{vb:.3f} -> {vn:.3f} ({delta:+.2%}, noise band "
                     f"±{band:.2%})")})
    if not verdicts:
        verdicts.append({"kind": "verdict", "check": check,
                         "metric": "*", "status": "fail",
                         "note": "evidence files carry no gated metrics"})
    return verdicts


def judge_decode(history: List[Tuple[int, dict]],
                 floors: dict = DECODE_FLOORS,
                 noise_floor: float = DEFAULT_NOISE_FLOOR) -> List[dict]:
    """Serving-decode ladder gate (see :func:`_judge_ladder`)."""
    return _judge_ladder(
        "decode", history, floors, noise_floor,
        "no pr*_decode_bench.jsonl evidence committed")


def judge_fleet(history: List[Tuple[int, dict]],
                floors: dict = FLEET_FLOORS,
                noise_floor: float = DEFAULT_NOISE_FLOOR) -> List[dict]:
    """Routed-fleet ladder gate (see :func:`_judge_ladder`): affinity
    advantage strictly positive, replica-kill success rate 1.0, KV
    handoff token-identical — the DESIGN.md §22 acceptance bars."""
    return _judge_ladder(
        "fleet", history, floors, noise_floor,
        "no pr*_fleet_probe.jsonl evidence committed "
        "(run benchmarks/fleet_probe.py --jsonl)")


def judge_soak(history: List[Tuple[int, dict]],
               floors: dict = SOAK_FLOORS,
               noise_floor: float = DEFAULT_NOISE_FLOOR) -> List[dict]:
    """Chaos-soak ladder gate (see :func:`_judge_ladder`): budget spent,
    every authority killed, the three flywheel invariants intact, and
    the leak forensic drill caught — the DESIGN.md §24 acceptance bars."""
    return _judge_ladder(
        "soak", history, floors, noise_floor,
        "no pr*_soak.jsonl evidence committed "
        "(run benchmarks/soak.py)")


# -- CLI --------------------------------------------------------------------

def _emit(verdicts: List[dict], out_path: Optional[str]) -> int:
    for v in verdicts:
        print(json.dumps(v, sort_keys=True))
    if out_path:
        with open(out_path, "w") as f:
            for v in verdicts:
                f.write(json.dumps(v, sort_keys=True) + "\n")
    failed = [v for v in verdicts if v["status"] == "fail"]
    print(f"# regression_gate: {len(verdicts) - len(failed)} pass, "
          f"{len(failed)} fail", file=sys.stderr)
    return 1 if failed else 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python benchmarks/regression_gate.py",
        description="Judge benchmark results against the committed "
                    "BENCH_r*.json release ladder; exit 1 on regression.")
    ap.add_argument("--check",
                    choices=("history", "fresh", "phases", "decode",
                             "roofline", "fleet", "soak"),
                    default="history")
    ap.add_argument("--repo-dir", default=REPO,
                    help="directory holding BENCH_r*.json")
    ap.add_argument("--fresh", metavar="PATH", default=None,
                    help="fresh benchmark result JSON (bench.py 'parsed' "
                         "shape, or a full BENCH doc) for --check fresh")
    ap.add_argument("--metrics", default=",".join(HEADLINE_METRICS),
                    help="comma-separated parsed-dict keys to judge")
    ap.add_argument("--lookback", type=int, default=DEFAULT_LOOKBACK,
                    help="history: releases back to compare against")
    ap.add_argument("--min-improvement", type=float,
                    default=DEFAULT_MIN_IMPROVEMENT,
                    help="history: required relative gain over lookback")
    ap.add_argument("--noise-floor", type=float,
                    default=DEFAULT_NOISE_FLOOR,
                    help="fresh: minimum noise band (relative)")
    ap.add_argument("--phases-baseline", metavar="PATH", default=None)
    ap.add_argument("--phases-fresh", metavar="PATH", default=None)
    ap.add_argument("--phase-budget", type=float,
                    default=DEFAULT_PHASE_BUDGET,
                    help="phases: max absolute growth in window share")
    ap.add_argument("--op-budget", type=float, default=DEFAULT_OP_BUDGET,
                    help="roofline: max absolute growth in an op's share "
                         "of modeled step time")
    ap.add_argument("--out", metavar="PATH", default=None,
                    help="also write verdict JSONL here")
    args = ap.parse_args(argv)
    metrics = tuple(m for m in args.metrics.split(",") if m)

    if args.check == "history":
        verdicts = judge_history(load_history(args.repo_dir),
                                 metrics=metrics, lookback=args.lookback,
                                 min_improvement=args.min_improvement)
    elif args.check == "fresh":
        if not args.fresh:
            ap.error("--check fresh requires --fresh PATH")
        with open(args.fresh) as f:
            doc = json.load(f)
        fresh = doc.get("parsed", doc)  # accept either shape
        verdicts = judge_fresh(load_history(args.repo_dir), fresh,
                               metrics=metrics,
                               noise_floor=args.noise_floor)
    elif args.check == "decode":
        verdicts = judge_decode(load_decode_history(args.repo_dir),
                                noise_floor=args.noise_floor)
    elif args.check == "fleet":
        verdicts = judge_fleet(load_fleet_history(args.repo_dir),
                               noise_floor=args.noise_floor)
    elif args.check == "soak":
        verdicts = judge_soak(load_soak_history(args.repo_dir),
                              noise_floor=args.noise_floor)
    elif args.check == "roofline":
        verdicts = judge_roofline(load_roofline_history(args.repo_dir),
                                  op_budget=args.op_budget)
    else:
        if not (args.phases_baseline and args.phases_fresh):
            ap.error("--check phases requires --phases-baseline and "
                     "--phases-fresh")
        verdicts = judge_phases(args.phases_baseline, args.phases_fresh,
                                budget_frac=args.phase_budget)
    return _emit(verdicts, args.out)


if __name__ == "__main__":
    sys.exit(main())
