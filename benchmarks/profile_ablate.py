"""Ablation profiler for the ResNet-50 MFU push (VERDICT r2 ask #1).

Times isolated pieces of the flagship benchmark on the real chip so the MFU
work is measured, not guessed. Each ablation reports ms/step and the implied
MFU computed against the FULL model's analytic FLOPs — so an ablation row
answers "what would the full model's MFU be if this component were free".

Run: python benchmarks/profile_ablate.py [--quick]
Findings land in DESIGN.md ("Round-3 profile" section).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu import engine, observability
from distkeras_tpu.models import resnet as resnet_lib
from distkeras_tpu.ops import optimizers as opt_lib

BATCH = 128
SIDE = 224
CLASSES = 1000
SCAN = 24  # steps per device call; large enough to amortize dispatch


def sync_via_fetch(out):
    """device->host fetch of one scalar: the completion barrier (see
    bench.py)."""
    leaf = jax.tree.leaves(out)[0]
    float(np.asarray(leaf).ravel()[0])


def timeit(fn, carry, batch, reps=3, warmup=2):
    """fn(carry, batch) -> carry, with carry donated: thread it through.
    Returns median seconds per call."""
    for _ in range(warmup):
        carry = fn(carry, batch)
        sync_via_fetch(carry)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        carry = fn(carry, batch)
        sync_via_fetch(carry)
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def scanned(step_fn, n=SCAN):
    def run(carry, batch):
        def body(c, _):
            return step_fn(c, batch), None

        carry, _ = jax.lax.scan(body, carry, None, length=n)
        return carry

    return jax.jit(run, donate_argnums=(0,))


def make_batch(dtype=jnp.float32, classes=CLASSES, batch=BATCH):
    rng = np.random.default_rng(0)
    if dtype == jnp.uint8:
        x = jnp.asarray(rng.integers(0, 256, (batch, SIDE, SIDE, 3),
                                     dtype=np.uint8))
    else:
        x = jnp.asarray(
            rng.standard_normal((batch, SIDE, SIDE, 3)).astype(np.float32),
            dtype)
    y = np.zeros((batch, classes), np.float32)
    y[np.arange(batch), rng.integers(0, CLASSES, batch)] = 1.0
    return {"features": jax.device_put(x),
            "labels": jax.device_put(jnp.asarray(y))}


def build(model, loss="categorical_crossentropy", lr=0.05, batch=BATCH):
    import optax

    tx = opt_lib.get("sgd", lr)
    rng = jax.random.key(0)
    sample = {"features": jnp.zeros((batch, SIDE, SIDE, 3), jnp.float32)}
    state = engine.create_train_state(model, rng, sample, tx)
    grad_fn = engine.make_grad_fn(model, loss)

    def step(carry, batch):
        params, opt_state = carry
        (_, _), grads = grad_fn(params, batch, None)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return (params, opt_state)

    return state, step


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true")
    p.add_argument("--only", default="",
                   help="comma-separated case keys to run (default: all)")
    args = p.parse_args()
    reps = 2 if args.quick else 3
    only = set(args.only.split(",")) - {""}

    peak = observability.device_peak_flops()
    if peak is None:
        peak = 197e12
        print("# WARNING: not on TPU, assuming v5e peak for the math")

    # dispatch overhead of one device call on this backend
    tiny = jax.jit(lambda c, b: (c[0] + 1.0, c[1]), donate_argnums=(0,))
    t_disp = timeit(tiny, (jnp.float32(0), jnp.float32(0)),
                    None, reps=reps)
    print(f"# per-call dispatch+fetch overhead: {t_disp*1e3:.1f} ms "
          f"(amortized over {SCAN}-step scans below: "
          f"{t_disp/SCAN*1e3:.2f} ms/step)")

    model = resnet_lib.resnet50(num_classes=CLASSES)
    state, step = build(model)
    flops = observability.count_flops(
        lambda c, b: step(c, b), (state.params, state.opt_state),
        make_batch())
    print(f"# analytic matmul/conv FLOPs per step: {flops/1e12:.3f} T "
          f"(peak {peak/1e12:.0f} T)")
    del state

    results = {}

    def run_case(key, label, model=None, batch_dtype=jnp.float32,
                 classes=CLASSES, fwd_only=False, batch_n=BATCH):
        if only and key not in only:
            return
        model = model or resnet_lib.resnet50(num_classes=classes)
        st, stp = build(model, batch=batch_n)
        batch = make_batch(batch_dtype, classes, batch=batch_n)
        if fwd_only:
            def stp(c, b):  # noqa: F811
                params, o, acc = c
                out = model.apply({"params": params}, b["features"],
                                  train=True)
                return (params, o, acc + out.astype(jnp.float32).mean())

            carry = (st.params, st.opt_state, jnp.float32(0))
            # forward-only can't donate params usefully; don't donate
            def run(carry, batch):
                def body(c, _):
                    return stp(c, batch), None
                c, _ = jax.lax.scan(body, carry, None, length=SCAN)
                return c

            fn = jax.jit(run)
            t = timeit(fn, carry, batch, reps=reps) / SCAN
        else:
            fn = scanned(stp)
            t = timeit(fn, (st.params, st.opt_state), batch,
                       reps=reps) / SCAN
        scale = batch_n / BATCH  # flops scale linearly with batch
        mfu = flops * scale / (t * peak)
        print(f"{label:46s} {t*1e3:8.2f} ms/step   "
              f"implied-MFU {mfu*100:5.1f}%")
        results[key] = t

    run_case("plain_step", "scan fwd+bwd+sgd (no substrate)")
    run_case("fwd_only", "scan forward only", fwd_only=True)

    # GroupNorm -> bias-only: end-to-end cost of the norms
    import flax.linen as nn

    class _Bias(nn.Module):
        @nn.compact
        def __call__(self, x):
            b = self.param("bias", nn.initializers.zeros, (x.shape[-1],),
                           jnp.float32)
            return x + b.astype(x.dtype)

    orig = resnet_lib.group_norm
    resnet_lib.group_norm = (
        lambda channels, dtype, name, **kw: _Bias(name=name))
    try:
        run_case("no_norm", "scan step, GroupNorm -> bias-only")
    finally:
        resnet_lib.group_norm = orig

    run_case("bf16_input", "scan step, bf16 input images",
             batch_dtype=jnp.bfloat16)
    run_case("head1024", "scan step, head padded to 1024", classes=1024)
    run_case("f32_model", "scan step, f32 compute",
             model=resnet_lib.resnet50(num_classes=CLASSES,
                                       dtype=jnp.float32))
    run_case("nf", "scan step, NF (scaled-WS, norm-free)",
             model=resnet_lib.resnet50(num_classes=CLASSES, norm="nf"))
    run_case("nf_s2d", "scan step, NF + space-to-depth stem",
             model=resnet_lib.resnet50(num_classes=CLASSES, norm="nf",
                                       space_to_depth=True),
             batch_dtype=jnp.uint8)
    run_case("nf_u8", "scan step, NF + uint8 input",
             model=resnet_lib.resnet50(num_classes=CLASSES, norm="nf"),
             batch_dtype=jnp.uint8)
    try:
        run_case("nf_u8_b256", "scan step, NF + uint8, batch 256",
                 model=resnet_lib.resnet50(num_classes=CLASSES, norm="nf"),
                 batch_dtype=jnp.uint8, batch_n=256)
    except Exception as e:
        print(f"# batch-256 case failed: {type(e).__name__}: {e}")

    if "plain_step" in results:
        print("\n# deltas vs plain step:")
        base = results["plain_step"]
        for k, v in results.items():
            if k == "plain_step":
                continue
            print(f"  {k:14s} {1e3*(v-base):+8.2f} ms/step "
                  f"({(v-base)/base*100:+5.1f}%)")


if __name__ == "__main__":
    main()
