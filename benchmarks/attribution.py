"""Step-time attribution: where each host_async window's wall-time went.

The profiling plane (PR 10, DESIGN.md §15) decomposes every worker window
into the ``profile.phase.*_s`` histograms — data wait, pull, h2d, compute,
commit, bookkeep at the top level (a PARTITION of the window), with
encode/decode/fold/collective nested inside them. This tool renders that
decomposition into the one question a tuning session starts from: which
phase is eating the gap between measured throughput and the chip's peak.

Two modes:

  python benchmarks/attribution.py <run.telemetry.jsonl>
      Render the phase table + residual attribution from an existing
      artifact (``Trainer(telemetry_path=...)``, ``dump_telemetry()``, or
      a collector-merged dump). Exits nonzero when the top-level phases
      cover less than --min-coverage of the window wall-time (default
      0.95) — a decomposition that loses >5% is naming the wrong
      bottleneck.

  python benchmarks/attribution.py --run [--out results/...jsonl]
      Self-contained CPU-host evidence run: a resnet18 host_async session
      (2 workers against a live DynSGD parameter server), measured twice
      per tracing mode in alternation — trace on (per-window
      TraceContexts + wire propagation) vs trace off (plain span events)
      — asserting the tracing overhead stays <= --max-overhead (default
      2%) of mean window time, then writing the phase decomposition +
      overhead comparison as a JSONL evidence artifact.

Attribution honesty: ``compute`` is the only phase doing model FLOPs, so
the "top residual" is simply the largest non-compute phase — named, with
its share. The gap to peak FLOPs is only quantified when the artifact
carries an ``observability.mfu`` gauge or the host has a known
accelerator peak (CPU has none); otherwise the residual is ranked by
window share alone and the report says so.

No third-party deps beyond the package's own stack; jax imports are
deferred into --run so rendering an artifact stays accelerator-free.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

try:
    import distkeras_tpu  # noqa: F401  (pip-installed)
except ImportError:  # running from a source checkout: use the repo root
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

#: top-level phases: by construction (host_async._serial_rounds) these
#: PARTITION each window — their sums should cover ~all of window_s
PARTITION = ("data_wait", "pull", "h2d", "compute", "commit", "bookkeep")
#: nested sub-phases (inside pull/commit/compute): shown, not summed
NESTED = ("encode", "decode", "fold", "collective")


def phase_table(rows: list) -> dict:
    """Aggregate ``profile.phase.<x>_s`` histogram rows (across worker
    labels) into ``{phase: {"sum_s": ..., "count": ...}}``."""
    out: dict = {}
    prefix, suffix = "profile.phase.", "_s"
    for r in rows:
        name = r.get("name", "")
        if (r.get("kind") != "histogram" or not name.startswith(prefix)
                or not name.endswith(suffix)):
            continue
        phase = name[len(prefix):-len(suffix)]
        agg = out.setdefault(phase, {"sum_s": 0.0, "count": 0})
        agg["sum_s"] += float(r.get("sum", 0.0))
        agg["count"] += int(r.get("count", 0))
    return out


def decompose(rows: list) -> dict:
    """The decomposition summary: total window seconds, per-phase seconds
    and window fractions, and the partition's coverage of the window."""
    table = phase_table(rows)
    window = table.get("window", {}).get("sum_s", 0.0)
    phases = {}
    for phase, agg in sorted(table.items()):
        if phase == "window":
            continue
        phases[phase] = {
            "sum_s": round(agg["sum_s"], 6), "count": agg["count"],
            "frac": round(agg["sum_s"] / window, 4) if window else None,
        }
    covered = sum(table.get(p, {}).get("sum_s", 0.0) for p in PARTITION)
    return {
        "window_s": round(window, 6),
        "phases": phases,
        "coverage": round(covered / window, 4) if window else None,
    }


def _mfu_from_rows(rows: list):
    for r in rows:
        if r.get("kind") == "gauge" and r.get("name") == "observability.mfu":
            return float(r["value"]), (r.get("labels") or {}).get("dtype")
    return None, None


def report(rows: list) -> str:
    """Human rendering: phase table, coverage, and the named residual."""
    d = decompose(rows)
    out = [f"# step-time attribution  (window total "
           f"{d['window_s'] * 1e3:.1f} ms over "
           f"{phase_table(rows).get('window', {}).get('count', 0)} windows)"]
    if not d["phases"]:
        return out[0] + "\nno profile.phase.* histograms in this artifact"
    width = max(len(p) for p in d["phases"])
    out.append(f"{'phase':{width}s} {'total_ms':>12s} {'share':>8s}  level")
    for phase, v in sorted(d["phases"].items(),
                           key=lambda kv: -kv[1]["sum_s"]):
        share = "-" if v["frac"] is None else f"{100 * v['frac']:.1f}%"
        level = "top" if phase in PARTITION else "nested"
        out.append(f"{phase:{width}s} {v['sum_s'] * 1e3:12.3f} "
                   f"{share:>8s}  {level}")
    if d["coverage"] is not None:
        out.append(f"\npartition coverage: {100 * d['coverage']:.1f}% of "
                   f"window wall-time (top-level phases)")
    residual = max(
        (p for p in d["phases"] if p in PARTITION and p != "compute"),
        key=lambda p: d["phases"][p]["sum_s"], default=None)
    if residual is not None:
        r = d["phases"][residual]
        mfu, dtype = _mfu_from_rows(rows)
        if mfu is not None:
            out.append(
                f"top residual: {residual} "
                f"({100 * (r['frac'] or 0):.1f}% of window) — largest "
                f"non-compute phase standing between the measured "
                f"{100 * mfu:.1f}% MFU ({dtype}) and peak")
        else:
            out.append(
                f"top residual: {residual} "
                f"({100 * (r['frac'] or 0):.1f}% of window) — largest "
                f"non-compute phase (no accelerator peak known on this "
                f"host; residual ranked by window share)")
    return "\n".join(out)


# -- op-level attribution (--ops, DESIGN.md §21) -----------------------------

#: reference ceilings for hosts without a local accelerator (CPU): the
#: roofline verdicts are computed against the v5e book numbers
#: (observability.DEVICE_PEAKS) so boundedness is
#: still deterministic and real — the report says which ceilings it used.
REF_DTYPE = "bf16"
REF_PEAK_FLOPS = 197e12
REF_HBM_BW = 819e9


def ops_report_from_rows(rows: list) -> str:
    """Render the op-level roofline section from an artifact's
    ``profile.op.*`` rows (the render-mode counterpart of the live
    RooflineReport). Degrades honestly: a backend that recorded
    ``profile.op.inventory_unavailable`` gets a no-cost-model verdict,
    not a zero-row table."""
    shares = []
    unavailable = False
    coverage = None
    for r in rows:
        name, kind = r.get("name"), r.get("kind")
        if kind == "gauge" and name == "profile.op.share":
            labels = r.get("labels") or {}
            shares.append((float(r.get("value", 0.0)),
                           labels.get("op", "?"),
                           labels.get("bound", "?")))
        elif kind == "gauge" and name == "profile.op.coverage":
            coverage = float(r.get("value", 0.0))
        elif kind == "counter" and name == "profile.op.inventory_unavailable" \
                and float(r.get("value", 0)) > 0:
            unavailable = True
        # the --ops --run evidence artifact's own row shapes render too
        elif kind == "op" and "share" in r:
            shares.append((float(r["share"]), r.get("op", "?"),
                           r.get("bound", "?")))
        elif kind == "roofline" and r.get("coverage") is not None:
            coverage = float(r["coverage"])
    out = ["", "# op-level roofline"]
    if not shares:
        if unavailable:
            out.append("no cost model on this backend "
                       "(profile.op.inventory_unavailable fired) — op "
                       "table honestly omitted")
        else:
            out.append("no profile.op.* rows in this artifact (run "
                       "attribution.py --ops --run, or the runner never "
                       "published a roofline)")
        return "\n".join(out)
    if coverage is not None:
        out.append(f"op rows cover {100 * coverage:.1f}% of the "
                   f"executable's modeled FLOPs")
    out.append(f"{'op':<40}{'bound':>8}{'share':>8}")
    for share, op, bound in sorted(shares, reverse=True):
        out.append(f"{op[:39]:<40}{bound:>8}{share:>7.1%}")
    return "\n".join(out)


def run_ops_evidence(out_path: str, workers: int = 2, rounds: int = 4,
                     batch: int = 8, window: int = 2, repeats: int = 2,
                     min_op_coverage: float = 0.90,
                     max_overhead: float = 0.02,
                     capture: bool = False, top_k: int = 8) -> dict:
    """The --ops --run evidence mode: one resnet18 host_async session,
    its compiled window executable walked into an op inventory, classified
    against the roofline, and rendered below the phase table.

    The paired off/on probe here toggles THIS PR's only default-path
    addition — the per-window MFU publication in bookkeep (off =
    ``mfu_peak_flops`` unknown, the CPU default; on = ceiling forced so
    the count/publish path runs every window) — pinning it at
    ``max_overhead``. Trace capture (``capture=True``) is the opt-in leg
    and is never part of the probe's "off" side; on CPU hosts it degrades
    to a typed no-device-plane verdict.
    """
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax

    from distkeras_tpu import observability, telemetry
    from distkeras_tpu import profiling
    from distkeras_tpu.models import resnet18
    from distkeras_tpu.parallel import host_async, strategies

    model = resnet18(num_classes=10, dtype=jnp.float32)
    runner = host_async.HostAsyncRunner(
        model, "categorical_crossentropy", optax.sgd(0.05),
        strategies.get("dynsgd"), window=window)
    shards = _staged_shards(workers, rounds, batch, window)
    init_params = model.init(
        jax.random.key(0), jnp.zeros((batch, 32, 32, 3), jnp.float32),
        train=False)["params"]

    telemetry.reset()
    runner.trace = False
    runner.mfu_peak_flops = REF_PEAK_FLOPS  # warm the counted-FLOPs cache
    runner.run(init_params, [shards])  # warmup: compile the window_fn

    # paired off/on probe (median of per-pair ratios of per-run median
    # window times, single worker). The order within each pair ALTERNATES:
    # host load drifts across back-to-back runs, and a fixed off-then-on
    # order folds that drift into the estimate with a consistent sign —
    # alternating cancels it across pairs.
    off_runs, on_runs = [], []
    for i in range(repeats):
        legs = [("off", None), ("on", REF_PEAK_FLOPS)]
        if i % 2:
            legs.reverse()
        for tag, ceiling in legs:
            runner.mfu_peak_flops = ceiling  # off: CPU default, path cold
            run = _measured_run(runner, init_params, shards[:1])
            (off_runs if tag == "off" else on_runs).append(run)
    pairs = sorted(on["window_p50_s"] / off["window_p50_s"] - 1.0
                   for off, on in zip(off_runs, on_runs))
    overhead = pairs[len(pairs) // 2] if len(pairs) % 2 else (
        pairs[len(pairs) // 2 - 1] + pairs[len(pairs) // 2]) / 2

    # op inventory of the ACTUAL compiled window executable, on the same
    # args the workers run (while_trips = the window scan's trip count)
    carry = runner.strategy.init_carry(init_params, runner.tx)
    batches = jax.device_put(shards[0][0], runner.devices[0])
    fold_key = np.int32(0)
    args = (jax.device_put(carry, runner.devices[0]),
            jax.device_put(init_params, runner.devices[0]), batches,
            fold_key)
    lowered = runner.window_fn.lower(*args)
    compiled = lowered.compile()
    inventory = profiling.op_inventory(compiled, while_trips=window)
    source = profiling.source_inventory(lowered, while_trips=window)
    analytic = observability.count_flops(runner.window_fn, *args)
    # coverage denominator: the PRE-optimization HLO for the SAME
    # executable, costed by the SAME shape arithmetic as the post-opt
    # inventory — same currency on both sides, so coverage measures what
    # the optimized executable retains of the modeled compute phase
    # rather than a parser-vs-XLA accounting mismatch (XLA's aggregate
    # undercounts dilated backward convs; the analytic MFU numerator
    # overcounts padding taps — both reported alongside, DESIGN.md §21
    # "honest limits").
    source_flops = (source.total_flops
                    if source.available and source.total_flops else None)
    denom = source_flops or inventory.xla_flops or analytic or None
    modeled = denom if denom else None

    measured = None
    capture_note = ""
    if capture:
        table = profiling.capture_op_times(
            lambda: runner.window_fn(*args), steps=3)
        if table.available:
            measured = table.seconds
        else:
            capture_note = table.note

    # the decomposition evidence comes from a full traced multi-worker
    # run; the roofline publishes into the same registry so the artifact
    # carries phase AND op rows together
    runner.trace = True
    reg = telemetry.reset()
    runner.run(init_params, [shards])
    report_obj = profiling.build_report(
        inventory, dtype=REF_DTYPE, peak_flops=REF_PEAK_FLOPS,
        hbm_bandwidth=REF_HBM_BW, measured=measured,
        modeled_flops=modeled, top_k=top_k)
    report_obj.publish()
    rows_on = list(reg.rows())
    telemetry.uninstall()
    d = decompose(rows_on)

    coverage = report_obj.coverage
    top = report_obj.top()
    lines = [
        {"kind": "meta", "tool": "attribution_ops", "model": "resnet18",
         "workers": workers, "rounds": rounds, "batch": batch,
         "window": window, "platform": jax.default_backend(),
         "ceilings": {"dtype": REF_DTYPE, "peak_flops": REF_PEAK_FLOPS,
                      "hbm_bw": REF_HBM_BW,
                      "reference": jax.default_backend() != "tpu"}},
        {"kind": "roofline",
         "coverage": None if coverage is None else round(coverage, 4),
         "inventory_flops": inventory.total_flops,
         "source_flops": source_flops,
         "xla_flops": inventory.xla_flops,
         "analytic_flops": analytic,
         "while_trips": window,
         "op_rows": len(inventory.rows),
         "measured_share": round(report_obj.measured_share, 4),
         "capture": bool(capture), "capture_note": capture_note},
        {"kind": "overhead",
         "window_p50_off_s": round(
             min(r["window_p50_s"] for r in off_runs), 6),
         "window_p50_on_s": round(
             min(r["window_p50_s"] for r in on_runs), 6),
         "pair_ratios": [round(p, 6) for p in pairs],
         "overhead_frac": round(overhead, 6), "repeats": repeats,
         "order": "alternated",
         "toggle": "per-window mfu publication"},
    ]
    for r in top:
        lines.append(r.to_row())
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")

    print(report(rows_on))
    print()
    print(report_obj.render())
    if analytic and inventory.total_flops:
        print(f"(inventory / analytic MFU-numerator flops: "
              f"{inventory.total_flops / analytic:.2f}x — the tap-exact "
              f"cost model skips the padding and dilation-zero taps the "
              f"naive transposed-conv model counts)")
    if capture:
        print("capture: " + ("joined measured op times"
                             if measured else f"declined ({capture_note})"))
    print(f"\nmfu-publication overhead: {100 * overhead:+.2f}% of median "
          f"window\nwrote {out_path}")

    ok = True
    if not inventory.available:
        print(f"no cost model on this backend ({inventory.note}) — "
              f"roofline verdict honestly omitted")
        ok = False
    elif coverage is None or coverage < min_op_coverage:
        print(f"FAIL: op coverage {coverage} < {min_op_coverage}")
        ok = False
    else:
        lead = top[0]
        print(f"top residual op: {lead.op} ({lead.bound}-bound, "
              f"{100 * lead.share:.1f}% of modeled step time) — fix: "
              f"{lead.fix}")
    if overhead > max_overhead:
        print(f"FAIL: mfu-publication overhead {overhead:.4f} > "
              f"{max_overhead}")
        ok = False
    return {"ok": ok, "coverage": coverage, "overhead_frac": overhead,
            "report": report_obj}


def run_attention_evidence(out_path: str, batch: int = 4, seq: int = 128,
                           top_k: int = 12, min_op_coverage: float = 0.90):
    """PR 18 evidence: does the fused flash-attention kernel shrink the
    attention group's share of the gpt grad step?

    Two legs in ONE artifact so the gate can compare within-file:

    - baseline (``kind="op_baseline"``): gpt_tiny with ``attention="full"``
      — the XLA einsum-softmax path — compiled and op-inventoried exactly
      like ``--ops --run`` does for resnet18, classified against the same
      reference v5e ceilings.
    - variant (``kind="op"``): the same rows with every
      ``pallas-attention``-tagged group replaced by ONE kernel-modeled row:
      FLOPs and bytes from ``flash_attention.modeled_train_cost`` (FLOPs
      INCLUDE the backward's recompute — charged against the kernel, not
      hidden; bytes are linear in T because the [T, T] logits never reach
      HBM), est_time re-derived against the same ceilings, all shares
      renormalized over the new total.

    The substitution is analytic because this host has no TPU: interpret
    mode lowers to the same XLA ops, so the kernel cannot appear in a CPU
    executable's HLO. The meta row says ``"modeled_substitution": true``
    — the same honesty convention as kernel_ablate's ``no-tpu-evidence``
    verdict — and records why no flagship BENCH ladder round accompanies
    this PR.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distkeras_tpu import engine, observability, profiling
    from distkeras_tpu.models.gpt import gpt_tiny
    from distkeras_tpu.ops.pallas import flash_attention as fa
    from distkeras_tpu.profiling.roofline import RooflineRow

    model = gpt_tiny(attention="full", max_len=seq)
    rng = np.random.default_rng(0)
    batch_d = {
        "features": jnp.asarray(
            rng.integers(1, 250, (batch, seq)).astype(np.int32)),
        "labels": jnp.asarray(
            rng.integers(1, 250, (batch, seq)).astype(np.int32)),
    }
    params = model.init(jax.random.key(0), batch_d["features"],
                        train=False)["params"]
    grad_fn = engine.make_grad_fn(model, "masked_lm")

    def step(params, batch):
        (loss_val, _), grads = grad_fn(params, batch)
        return loss_val, grads

    args = (params, batch_d)
    lowered = jax.jit(step).lower(*args)
    compiled = lowered.compile()
    inventory = profiling.op_inventory(compiled)
    source = profiling.source_inventory(lowered)
    try:
        analytic = observability.count_flops(step, *args)
    except Exception:
        analytic = None
    source_flops = (source.total_flops
                    if source.available and source.total_flops else None)
    denom = source_flops or inventory.xla_flops or analytic or None
    report_obj = profiling.build_report(
        inventory, dtype=REF_DTYPE, peak_flops=REF_PEAK_FLOPS,
        hbm_bandwidth=REF_HBM_BW, modeled_flops=denom, top_k=top_k)
    coverage = report_obj.coverage

    att = [r for r in report_obj.rows if r.fix == "pallas-attention"]
    rest = [r for r in report_obj.rows if r.fix != "pallas-attention"]
    head_dim = model.width // model.num_heads
    q_shape = (batch, seq, model.num_heads, head_dim)
    kernel_fits = fa.fits(q_shape)
    dtype_bytes = jnp.dtype(model.dtype).itemsize
    k_flops, k_bytes = fa.modeled_train_cost(
        q_shape, dtype_bytes=dtype_bytes, causal=True)
    k_flops *= model.num_layers
    k_bytes *= model.num_layers
    k_time = max(k_flops / REF_PEAK_FLOPS, k_bytes / REF_HBM_BW)
    k_bound = profiling.classify(k_flops, k_bytes,
                                 REF_PEAK_FLOPS, REF_HBM_BW)
    new_total = sum(r.est_time_s for r in rest) + k_time
    kernel_row = RooflineRow(
        op="fused-flash-attention (kernel-modeled)", opcode="pallas-call",
        bound=k_bound, flops=k_flops, bytes_accessed=k_bytes,
        intensity=(k_flops / k_bytes if k_bytes else None),
        est_time_s=k_time,
        headroom_s=max(0.0, k_time - k_flops / REF_PEAK_FLOPS),
        share=(k_time / new_total if new_total else 0.0),
        fix="pallas-attention", count=len(att), measured=False,
        fix_available=not fa.USE_FLASH_ATTENTION)
    variant = [RooflineRow(
        op=r.op, opcode=r.opcode, bound=r.bound, flops=r.flops,
        bytes_accessed=r.bytes_accessed, intensity=r.intensity,
        est_time_s=r.est_time_s, headroom_s=r.headroom_s,
        share=(r.est_time_s / new_total if new_total else 0.0),
        fix=r.fix, count=r.count, measured=r.measured,
        fix_available=r.fix_available) for r in rest]
    variant.append(kernel_row)

    def _rank(rows):
        return sorted(rows, key=lambda r: (-r.headroom_s, -r.est_time_s,
                                           r.op))

    base_write = _rank(report_obj.top()
                       + [r for r in att if r not in report_obj.top()])
    var_write = _rank(variant)[:top_k]
    if kernel_row not in var_write:
        var_write.append(kernel_row)

    att_share_base = sum(r.share for r in att)
    att_time_base = sum(r.est_time_s for r in att)
    shrink = att_share_base - kernel_row.share

    lines = [
        {"kind": "meta", "tool": "attribution_attention",
         "model": "gpt_tiny", "batch": batch, "seq": seq,
         "platform": jax.default_backend(),
         "ceilings": {"dtype": REF_DTYPE, "peak_flops": REF_PEAK_FLOPS,
                      "hbm_bw": REF_HBM_BW,
                      "reference": jax.default_backend() != "tpu"},
         "flag": "USE_FLASH_ATTENTION",
         "kernel_fits": kernel_fits,
         "modeled_substitution": True,
         "note": ("variant rows substitute the pallas-attention group "
                  "with flash_attention.modeled_train_cost at the "
                  "reference ceilings — no TPU on this host, so the "
                  "kernel cannot appear in a compiled HLO and no "
                  "flagship BENCH ladder round (bench.py, TPU-only) "
                  "could run; TPU validation path: "
                  "kernel_ablate.py --kernel flash_attention")},
        {"kind": "roofline",
         "coverage": None if coverage is None else round(coverage, 4),
         "inventory_flops": inventory.total_flops,
         "source_flops": source_flops,
         "xla_flops": inventory.xla_flops,
         "analytic_flops": analytic,
         "op_rows": len(inventory.rows),
         "measured_share": round(report_obj.measured_share, 4)},
    ]
    for r in base_write:
        lines.append(dict(r.to_row(), kind="op_baseline"))
    for r in var_write:
        lines.append(dict(r.to_row(), **(
            {"kernel_modeled": True} if r is kernel_row else {})))
    lines.append(
        {"kind": "attention",
         "share_baseline": round(att_share_base, 4),
         "share_variant": round(kernel_row.share, 4),
         "shrink": round(shrink, 4),
         "est_time_baseline_s": att_time_base,
         "est_time_kernel_s": k_time,
         "speedup_modeled": (round(att_time_base / k_time, 2)
                             if k_time else None)})
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")

    print(report_obj.render())
    print(f"\nattention group: {len(att)} op row(s), "
          f"{100 * att_share_base:.1f}% of modeled step time "
          f"(baseline) -> {100 * kernel_row.share:.1f}% kernel-modeled "
          f"({att_time_base / k_time:.1f}x on the attention group alone)"
          if k_time else "\nattention group: empty")
    print(f"wrote {out_path}")

    ok = True
    if not inventory.available:
        print(f"no cost model on this backend ({inventory.note})")
        ok = False
    elif coverage is None or coverage < min_op_coverage:
        print(f"FAIL: op coverage {coverage} < {min_op_coverage}")
        ok = False
    if not att:
        print("FAIL: no pallas-attention-tagged rows in the baseline "
              "inventory — nothing to substitute")
        ok = False
    if not kernel_fits:
        print(f"FAIL: flash_attention.fits({q_shape}) is false — the "
              f"substitution would claim a dispatch that cannot happen")
        ok = False
    if shrink <= 0:
        print(f"FAIL: modeled attention share did not shrink "
              f"({att_share_base:.4f} -> {kernel_row.share:.4f})")
        ok = False
    return {"ok": ok, "coverage": coverage, "shrink": shrink,
            "share_baseline": att_share_base,
            "share_variant": kernel_row.share}


# -- the --run evidence mode -------------------------------------------------

def _staged_shards(num_workers: int, rounds: int, batch: int,
                   window: int, seed: int = 0) -> list:
    import numpy as np

    rng = np.random.default_rng(seed)
    shards = []
    for _ in range(num_workers):
        rs = []
        for _ in range(rounds):
            x = rng.standard_normal(
                (window, batch, 32, 32, 3)).astype(np.float32)
            y = np.eye(10, dtype=np.float32)[
                rng.integers(0, 10, (window, batch))]
            rs.append({"features": x, "labels": y})
        shards.append(rs)
    return shards


def _measured_run(runner, init_params, shards) -> dict:
    """One measured host_async run: fresh registry, mean window time +
    the full row dump."""
    from distkeras_tpu import telemetry

    reg = telemetry.reset()
    runner.run(init_params, [shards])
    rows = list(reg.rows())
    p50s = [float(r["p50"]) for r in rows
            if r.get("kind") == "histogram" and r.get("p50") is not None
            and r.get("name") == "profile.phase.window_s"]
    table = phase_table(rows)
    win = table.get("window", {"sum_s": 0.0, "count": 0})
    return {"rows": rows,
            "window_mean_s": win["sum_s"] / max(1, win["count"]),
            "window_p50_s": min(p50s) if p50s else 0.0}


def run_evidence(out_path: str, workers: int = 2, rounds: int = 4,
                 batch: int = 8, window: int = 2, repeats: int = 2,
                 min_coverage: float = 0.95,
                 max_overhead: float = 0.02) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from distkeras_tpu import telemetry
    from distkeras_tpu.models import resnet18
    from distkeras_tpu.parallel import host_async, strategies

    model = resnet18(num_classes=10, dtype=jnp.float32)
    runner = host_async.HostAsyncRunner(
        model, "categorical_crossentropy", optax.sgd(0.05),
        strategies.get("dynsgd"), window=window)
    shards = _staged_shards(workers, rounds, batch, window)
    init_params = model.init(
        jax.random.key(0), jnp.zeros((batch, 32, 32, 3), jnp.float32),
        train=False)["params"]

    telemetry.reset()
    runner.trace = False
    runner.run(init_params, [shards])  # warmup: compile the window_fn

    # Overhead measurement: single worker, so XLA's intra-op thread pool
    # isn't oversubscribed by concurrent worker threads — under that
    # contention window timing jitters by several %, swamping the
    # microseconds a span record costs. Runs alternate off/on so host
    # drift hits each PAIR about equally; the estimator is the median of
    # the per-pair ratios of per-run MEDIAN window times — robust both to
    # slow drift (paired) and to outlier windows (double median).
    off_runs, on_runs = [], []
    for _ in range(repeats):
        runner.trace = False
        off_runs.append(_measured_run(runner, init_params, shards[:1]))
        runner.trace = True
        on_runs.append(_measured_run(runner, init_params, shards[:1]))
    pairs = sorted(on["window_p50_s"] / off["window_p50_s"] - 1.0
                   for off, on in zip(off_runs, on_runs))
    overhead = pairs[len(pairs) // 2] if len(pairs) % 2 else (
        pairs[len(pairs) // 2 - 1] + pairs[len(pairs) // 2]) / 2
    off_s = min(r["window_p50_s"] for r in off_runs)
    on_s = min(r["window_p50_s"] for r in on_runs)

    # the decomposition evidence comes from a full traced multi-worker run
    runner.trace = True
    rows_on = _measured_run(runner, init_params, shards)["rows"]
    telemetry.uninstall()
    d = decompose(rows_on)
    traced = sum(1 for r in rows_on
                 if r.get("kind") == "span" and "trace_id" in r)
    result = {
        "decomposition": d,
        "overhead": {
            "window_p50_off_s": round(off_s, 6),
            "window_p50_on_s": round(on_s, 6),
            "pair_ratios": [round(p, 6) for p in pairs],
            "overhead_frac": round(overhead, 6),
            "repeats": repeats,
        },
        "traced_spans": traced,
    }
    lines = [
        {"kind": "meta", "tool": "attribution", "model": "resnet18",
         "workers": workers, "rounds": rounds, "batch": batch,
         "window": window, "platform": jax.default_backend()},
        {"kind": "decomposition", **d},
        {"kind": "overhead", **result["overhead"],
         "traced_spans": traced},
    ]
    for phase, v in d["phases"].items():
        lines.append({"kind": "phase", "phase": phase,
                      "level": "top" if phase in PARTITION else "nested",
                      **v})
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
    print(report(rows_on))
    print(f"\ntracing overhead: {100 * overhead:+.2f}% of median window "
          f"({off_s * 1e3:.1f} ms off -> {on_s * 1e3:.1f} ms on); "
          f"{traced} traced spans\nwrote {out_path}")
    ok = True
    if d["coverage"] is None or d["coverage"] < min_coverage:
        print(f"FAIL: phase coverage {d['coverage']} < {min_coverage}")
        ok = False
    if overhead > max_overhead:
        print(f"FAIL: tracing overhead {overhead:.4f} > {max_overhead}")
        ok = False
    result["ok"] = ok
    return result


def run_recorder_evidence(out_path: str, workers: int = 2,
                          rounds: int = 4, batch: int = 8, window: int = 2,
                          repeats: int = 2,
                          max_overhead: float = 0.02) -> dict:
    """Flight-recorder cost evidence: the same paired off/on harness as
    :func:`run_evidence`, but the toggle is the telemetry RECORDER sink
    (off = no recorder installed, on = a fresh
    :class:`~distkeras_tpu.health.recorder.FlightRecorder`) with tracing
    held constant. What the "on" side pays per window: one
    ``window_profile`` ring append + the span-event forwards."""
    import jax
    import jax.numpy as jnp
    import optax

    from distkeras_tpu import telemetry
    from distkeras_tpu.health import recorder as recorder_mod
    from distkeras_tpu.health.recorder import FlightRecorder
    from distkeras_tpu.models import resnet18
    from distkeras_tpu.parallel import host_async, strategies

    model = resnet18(num_classes=10, dtype=jnp.float32)
    runner = host_async.HostAsyncRunner(
        model, "categorical_crossentropy", optax.sgd(0.05),
        strategies.get("dynsgd"), window=window)
    shards = _staged_shards(workers, rounds, batch, window)
    init_params = model.init(
        jax.random.key(0), jnp.zeros((batch, 32, 32, 3), jnp.float32),
        train=False)["params"]

    telemetry.reset()
    runner.trace = False
    telemetry.set_recorder(None)
    runner.run(init_params, [shards])  # warmup: compile the window_fn

    off_runs, on_runs = [], []
    ring_events = 0
    try:
        for _ in range(repeats):
            telemetry.set_recorder(None)
            off_runs.append(_measured_run(runner, init_params, shards[:1]))
            rec = FlightRecorder()
            telemetry.set_recorder(rec)
            on_runs.append(_measured_run(runner, init_params, shards[:1]))
            ring_events = len(rec.events())
    finally:
        # put the process's default-on recorder back whatever happens
        telemetry.set_recorder(recorder_mod.get_recorder())
        telemetry.uninstall()
    pairs = sorted(on["window_p50_s"] / off["window_p50_s"] - 1.0
                   for off, on in zip(off_runs, on_runs))
    overhead = pairs[len(pairs) // 2] if len(pairs) % 2 else (
        pairs[len(pairs) // 2 - 1] + pairs[len(pairs) // 2]) / 2
    off_s = min(r["window_p50_s"] for r in off_runs)
    on_s = min(r["window_p50_s"] for r in on_runs)

    lines = [
        {"kind": "meta", "tool": "recorder_overhead", "model": "resnet18",
         "workers": 1, "rounds": rounds, "batch": batch,
         "window": window, "platform": jax.default_backend()},
        {"kind": "overhead",
         "window_p50_off_s": round(off_s, 6),
         "window_p50_on_s": round(on_s, 6),
         "pair_ratios": [round(p, 6) for p in pairs],
         "overhead_frac": round(overhead, 6),
         "repeats": repeats,
         "ring_events_per_run": ring_events},
    ]
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
    print(f"flight-recorder overhead: {100 * overhead:+.2f}% of median "
          f"window ({off_s * 1e3:.1f} ms off -> {on_s * 1e3:.1f} ms on); "
          f"{ring_events} ring events per run\nwrote {out_path}")
    ok = overhead <= max_overhead
    if not ok:
        print(f"FAIL: recorder overhead {overhead:.4f} > {max_overhead}")
    return {"overhead_frac": overhead, "ok": ok}


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="phase attribution for host_async windows")
    ap.add_argument("path", nargs="?",
                    help="telemetry .jsonl to render (omit with --run)")
    ap.add_argument("--run", action="store_true",
                    help="execute the resnet18 CPU evidence run "
                         "(tracing on vs off) instead of rendering")
    ap.add_argument("--recorder-overhead", action="store_true",
                    help="execute the flight-recorder off/on paired cost "
                         "run instead (same harness, recorder sink as "
                         "the toggle)")
    ap.add_argument("--ops", action="store_true",
                    help="op-level attribution (DESIGN.md §21): with "
                         "--run, walk the compiled window executable into "
                         "a roofline report below the phase table; "
                         "without, render profile.op.* rows from the "
                         "artifact")
    ap.add_argument("--attention", action="store_true",
                    help="--ops --run: gpt attention-share evidence "
                         "(PR 18) instead of the resnet18 window — "
                         "baseline XLA attention vs the kernel-modeled "
                         "flash substitution, one artifact")
    ap.add_argument("--seq", type=int, default=128,
                    help="--attention: gpt sequence length (must satisfy "
                         "flash_attention.fits)")
    ap.add_argument("--capture", action="store_true",
                    help="--ops --run: ALSO run the opt-in jax.profiler "
                         "trace capture and join measured op times "
                         "(degrades to a typed verdict on CPU hosts)")
    ap.add_argument("--min-op-coverage", type=float, default=0.90,
                    help="--ops: fail when op rows cover less of the "
                         "executable's modeled FLOPs")
    ap.add_argument("--top-k", type=int, default=8,
                    help="--ops: roofline rows rendered/published")
    ap.add_argument("--out",
                    default=None,
                    help="evidence JSONL destination (default "
                         "results/pr10_attribution.jsonl for --run, "
                         "results/pr11_recorder_overhead.jsonl for "
                         "--recorder-overhead)")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--window", type=int, default=2)
    ap.add_argument("--repeats", type=int, default=2,
                    help="--run: alternating off/on measurement pairs")
    ap.add_argument("--min-coverage", type=float, default=0.95,
                    help="fail under this partition coverage of window "
                         "wall-time")
    ap.add_argument("--max-overhead", type=float, default=0.02,
                    help="--run: fail above this tracing-on overhead")
    args = ap.parse_args(argv)
    results_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "results")
    if args.recorder_overhead:
        out = args.out or os.path.join(results_dir,
                                       "pr11_recorder_overhead.jsonl")
        result = run_recorder_evidence(
            out, workers=args.workers, rounds=args.rounds,
            batch=args.batch, window=args.window, repeats=args.repeats,
            max_overhead=args.max_overhead)
        sys.exit(0 if result["ok"] else 1)
    if args.ops and args.run and args.attention:
        out = args.out or os.path.join(results_dir,
                                       "pr18_attribution_ops.jsonl")
        result = run_attention_evidence(
            out, batch=args.batch, seq=args.seq, top_k=args.top_k,
            min_op_coverage=args.min_op_coverage)
        sys.exit(0 if result["ok"] else 1)
    if args.ops and args.run:
        out = args.out or os.path.join(results_dir,
                                       "pr16_attribution_ops.jsonl")
        result = run_ops_evidence(
            out, workers=args.workers, rounds=args.rounds,
            batch=args.batch, window=args.window, repeats=args.repeats,
            min_op_coverage=args.min_op_coverage,
            max_overhead=args.max_overhead, capture=args.capture,
            top_k=args.top_k)
        sys.exit(0 if result["ok"] else 1)
    if args.run:
        out = args.out or os.path.join(results_dir,
                                       "pr10_attribution.jsonl")
        result = run_evidence(
            out, workers=args.workers, rounds=args.rounds,
            batch=args.batch, window=args.window, repeats=args.repeats,
            min_coverage=args.min_coverage, max_overhead=args.max_overhead)
        sys.exit(0 if result["ok"] else 1)
    if not args.path:
        ap.error("give a telemetry .jsonl path, or --run")
    from distkeras_tpu.telemetry import load_jsonl

    try:
        rows = load_jsonl(args.path)
    except OSError as e:
        sys.exit(f"cannot read {args.path}: {e}")
    print(report(rows))
    if args.ops:
        print(ops_report_from_rows(rows))
    d = decompose(rows)
    if d["coverage"] is not None and d["coverage"] < args.min_coverage:
        sys.exit(f"phase coverage {d['coverage']} < {args.min_coverage}")


if __name__ == "__main__":
    main()
