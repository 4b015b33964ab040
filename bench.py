"""Benchmark: flagship distributed training step on real hardware.

Runs the framework's actual distributed training machinery (substrate
epoch_fn: shard_map'd scanned rounds + psum center fold, ADAG strategy) on
ResNet-50 with synthetic ImageNet-shaped data, and prints ONE JSON line:

    {"metric": ..., "value": N, "unit": "samples/sec/chip", "vs_baseline": N}

The reference publishes no samples/sec numbers (BASELINE.md), so
``vs_baseline`` is measured against the driver's north star instead: the
throughput ResNet-50 would need on this chip to hit 50% MFU
(vs_baseline = achieved_MFU / 0.50). >1.0 beats the north star.
"""

from __future__ import annotations

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np


def run(batch_size: int, image_side: int, window: int, rounds: int,
        num_classes: int):
    from distkeras_tpu import engine, observability
    from distkeras_tpu.models.resnet import resnet50_nf
    from distkeras_tpu.ops import optimizers as opt_lib
    from distkeras_tpu.parallel import mesh as mesh_lib
    from distkeras_tpu.parallel import strategies, substrate

    mesh = mesh_lib.make_mesh(num_workers=1, devices=jax.devices()[:1])
    # the public ≥50%-MFU recipe (models/resnet.resnet50_nf): norm-free
    # scaled-WS ResNet-50 + on-device uint8 normalize (DESIGN.md §4b)
    model = resnet50_nf(num_classes=num_classes)
    tx = opt_lib.get("sgd", 0.05)
    strategy = strategies.get("adag", learning_rate=0.05)

    rng = jax.random.key(0)
    sample = {"features": jnp.zeros((batch_size, image_side, image_side, 3),
                                    jnp.float32)}
    state = engine.create_train_state(model, rng, sample, tx)
    center, carries = substrate.init_center_and_carries(
        state.params, tx, strategy, mesh, 1)
    epoch_fn = substrate.build_epoch_fn(
        model, "categorical_crossentropy", tx, strategy, mesh,
        num_workers=1, window=window, metrics=())

    rng_np = np.random.default_rng(0)
    # uint8 images, normalized on device — the realistic ImageNet input
    # path: 4x fewer staged HBM bytes than f32 (and 4x less host->device)
    feats = rng_np.integers(
        0, 256, (rounds, 1, window, batch_size, image_side, image_side, 3),
        dtype=np.uint8)
    labels = np.eye(num_classes, dtype=np.float32)[
        rng_np.integers(0, num_classes, (rounds, 1, window, batch_size))]
    data = jax.device_put({"features": feats, "labels": labels},
                          mesh_lib.round_major_sharded(mesh))

    # FLOPs of one epoch_fn call: analytic matmul/conv count from the jaxpr
    # (XLA cost_analysis underreports — see observability).
    flops_per_call = observability.count_flops(
        lambda c, ca, d: epoch_fn(c, ca, d, np.int32(0)),
        center, carries, data)

    import time

    def step(carry):
        center, carries = carry
        center, carries, ms = epoch_fn(center, carries, data, np.int32(0))
        return (center, carries), ms

    def sync(center, ms) -> float:
        # Completion barrier: ONE device->host fetch of a scalar of the
        # final center state — it depends on the whole program. (An
        # earlier installation needed the fetch because block_until_ready
        # returned early there; chip_smoke.py times one call both ways so
        # the benchmark PR can pick the barrier this machine needs.)
        return float(np.asarray(jax.tree.leaves(center)[0]).ravel()[0])

    # compile + settle
    for _ in range(2):
        (center, carries), ms = step((center, carries))
        sync(center, ms)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        (center, carries), ms = step((center, carries))
        sync(center, ms)
        times.append(time.perf_counter() - t0)
    step_time = sorted(times)[len(times) // 2]  # median: robust to stragglers

    samples_per_call = rounds * window * batch_size
    sps = samples_per_call / step_time
    return sps, observability.mfu(flops_per_call, step_time, num_chips=1)


def main():
    from distkeras_tpu import observability

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # the metric below is a chip metric; a CPU timing never wears it
        sys.exit(f"bench.py measures a TPU; jax.devices()[0].platform is "
                 f"{dev.platform!r}")
    # Scanned steps per device call amortize the host dispatch; window=16
    # (λ=16, a standard AGN setting — the commit is window-normalized so
    # the server step is λ-invariant) halves the center-fold count vs
    # window=8. Convergence side of the window choice: STALENESS_r05.json
    # / DESIGN.md §2b — at num_workers=1 there are no other committers
    # (staleness 0), so w16 is convergence-free here; the curve quantifies
    # what window costs at K=8, which is why the window is a measured
    # trade-off knob, not folklore. uint8 staging keeps the 384-step chunk
    # at ~7.4 GB HBM.
    sps, mfu_val = run(batch_size=128, image_side=224, window=16, rounds=24,
                       num_classes=1000)
    # a calibration that cannot run raises: an MFU whose methodology is
    # unchecked is exactly what this gate exists to stop
    cal_ratio = observability.calibrate_peak()["ratio"]
    # observability.CAL_BAND ((0.80, 1.05), justified there): outside it
    # an MFU would rest on a broken methodology invariant, so none is
    # printed (fail-closed)
    lo, hi = observability.CAL_BAND
    out = {"metric": "resnet50_adag_samples_per_sec_per_chip",
           "value": round(float(sps), 2), "unit": "samples/sec/chip",
           "vs_baseline": None,
           "calibration_ratio": round(float(cal_ratio), 4)}
    if lo <= cal_ratio <= hi:
        out["vs_baseline"] = round(float(mfu_val / 0.50), 4)
        out["mfu"] = round(float(mfu_val), 4)
    else:
        print(f"# calibration ratio {cal_ratio:.3f} outside ({lo}, {hi}): "
              f"refusing to report MFU (methodology invariant violated)",
              file=sys.stderr)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
