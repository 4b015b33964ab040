"""Cost-model sweep: op inventories + rooflines for the model zoo.

PR 16 satellite evidence (DESIGN.md §21): walk the compiled grad-step
executable of resnet18 / gpt_tiny / vit_tiny through
``profiling.op_inventory`` and classify every op group against the
reference v5e ceilings. The committed JSONL answers, per model, the
question the phase-level attribution table cannot: WHICH ops hold the
compute, and are they memory- or compute-bound at the reference chip?

Runs on a CPU host (JAX_PLATFORMS=cpu) — the inventory comes from the
post-optimization HLO of the *local* backend, so absolute FLOP totals
are honest for the CPU executable while the boundedness verdicts are
"what this HLO would look like against a v5e" (meta row says
``"reference": true``, same convention as attribution.py --ops).

Usage:
  python benchmarks/roofline_probe.py [--out results/pr16_roofline_probe.jsonl]
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

#: Reference chip for boundedness verdicts on hosts without a TPU
#: (v5e bf16 peak / HBM bandwidth; observability.DEVICE_PEAKS holds the
#: same numbers).
REF_DTYPE = "bf16"
REF_PEAK_FLOPS = 197e12
REF_HBM_BW = 819e9


def _models():
    """(name, model, batch, loss) per zoo member — tiny shapes, CPU-safe."""
    import numpy as np

    from distkeras_tpu.models.gpt import gpt_tiny
    from distkeras_tpu.models.resnet import resnet18
    from distkeras_tpu.models.vit import vit_tiny

    rng = np.random.default_rng(0)
    resnet_batch = {
        "features": rng.standard_normal((8, 32, 32, 3)).astype(np.float32),
        "labels": rng.integers(0, 10, (8,)).astype(np.int32),
    }
    gpt_batch = {
        "features": rng.integers(1, 250, (4, 32)).astype(np.int32),
        "labels": rng.integers(1, 250, (4, 32)).astype(np.int32),
    }
    vit_batch = {
        "features": rng.standard_normal((8, 16, 16, 3)).astype(np.float32),
        "labels": rng.integers(0, 10, (8,)).astype(np.int32),
    }
    return (
        ("resnet18", resnet18(num_classes=10), resnet_batch,
         "sparse_categorical_crossentropy"),
        ("gpt_tiny", gpt_tiny(), gpt_batch, "masked_lm"),
        ("vit_tiny", vit_tiny(num_classes=10), vit_batch,
         "sparse_categorical_crossentropy"),
    )


def probe_model(name, model, batch, loss, top_k: int = 8) -> dict:
    """Compile the grad step, inventory its ops, classify vs reference
    ceilings. Returns {"roofline": row, "ops": [rows...], "render": str}."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu import engine, observability, profiling

    params = model.init(jax.random.key(0),
                        jnp.asarray(batch["features"]),
                        train=False)["params"]
    grad_fn = engine.make_grad_fn(model, loss)

    def step(params, batch):
        (loss_val, _), grads = grad_fn(params, batch)
        return loss_val, grads

    args = (params, {k: jnp.asarray(v) for k, v in batch.items()})
    lowered = jax.jit(step).lower(*args)
    compiled = lowered.compile()
    inventory = profiling.op_inventory(compiled)
    source = profiling.source_inventory(lowered)
    try:
        analytic = observability.count_flops(step, *args)
    except Exception:
        analytic = None
    # same denominator as attribution --ops: the pre-optimization HLO
    # costed by the same shape arithmetic (fall back to XLA's aggregate,
    # then the analytic model, when a backend exposes no pre-opt text)
    source_flops = (source.total_flops
                    if source.available and source.total_flops else None)
    denom = source_flops or inventory.xla_flops or analytic or None
    report = profiling.build_report(
        inventory, dtype=REF_DTYPE, peak_flops=REF_PEAK_FLOPS,
        hbm_bandwidth=REF_HBM_BW, modeled_flops=denom, top_k=top_k)
    top = report.top()
    roofline_row = {
        "kind": "roofline", "model": name, "available": report.available,
        "coverage": (None if report.coverage is None
                     else round(report.coverage, 4)),
        "inventory_flops": inventory.total_flops,
        "source_flops": source_flops,
        "xla_flops": inventory.xla_flops,
        "analytic_flops": analytic,
        "op_rows": len(inventory.rows),
        "while_floor": inventory.while_floor,
        "top_op": top[0].op if top else None,
        "top_bound": top[0].bound if top else None,
        "note": report.note,
    }
    ops = [dict(r.to_row(), model=name) for r in top]
    return {"roofline": roofline_row, "ops": ops, "render": report.render()}


def run(out_path: str, top_k: int = 8) -> dict:
    import jax

    rows = [{
        "kind": "meta", "tool": "roofline_probe",
        "platform": jax.default_backend(),
        "dtype": REF_DTYPE, "peak_flops": REF_PEAK_FLOPS,
        "hbm_bandwidth": REF_HBM_BW,
        # verdicts are classified against the reference chip, not the
        # host backend the HLO was compiled for
        "reference": True,
    }]
    ok = True
    for name, model, batch, loss in _models():
        result = probe_model(name, model, batch, loss, top_k=top_k)
        print(f"== {name} ==")
        print(result["render"])
        r = result["roofline"]
        if not r["available"] or not r["op_rows"]:
            ok = False
        if r["coverage"] is not None:
            denom_name = ("pre-opt" if r["source_flops"]
                          else "XLA" if r["xla_flops"] else "analytic")
            print(f"coverage {r['coverage']:.1%} of "
                  f"{denom_name}-modeled FLOPs; "
                  f"top op {r['top_op']} ({r['top_bound']}-bound)")
        print()
        rows.append(r)
        rows.extend(result["ops"])
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    print(f"wrote {len(rows)} rows to {out_path}  ok={ok}")
    return {"ok": ok, "rows": rows}


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="op-inventory + roofline sweep over the model zoo")
    ap.add_argument("--out",
                    default=os.path.join(
                        os.path.dirname(os.path.abspath(__file__)),
                        "results", "pr16_roofline_probe.jsonl"))
    ap.add_argument("--top-k", type=int, default=8,
                    help="roofline rows kept per model")
    args = ap.parse_args(argv)
    result = run(args.out, top_k=args.top_k)
    sys.exit(0 if result["ok"] else 1)


if __name__ == "__main__":
    main()
