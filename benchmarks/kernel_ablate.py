"""Shared ablation harness for the Pallas kernel tier (DESIGN.md §23).

Every in-tree kernel earns its default-on flag HERE, on the target TPU
generation, never from a CPU run: each client times a plain-bf16
baseline, the XLA fallback the repo actually uses while the kernel is
off, and the compiled Pallas kernel itself. Off-TPU there is nothing to
time (interpret mode measures the interpreter), so the harness refuses to
run: a CPU run can never be mistaken for a speedup.

Clients (``--kernel``):

- ``int8_matmul``: fused scaled-int8 matmul-dequant vs XLA int8 dot vs
  bf16 matmul (``ops/pallas/int8_matmul.py``;
  ``benchmarks/int8_matmul_ablate.py`` is now a thin alias).
- ``flash_attention``: fused causal flash attention vs the XLA
  einsum-softmax path, bf16 and f32 inputs
  (``ops/pallas/flash_attention.py``).

Usage: python benchmarks/kernel_ablate.py --kernel NAME
       [--shapes SPEC[;SPEC...]] [--iters N]
One JSON line per (variant, shape) with the median of ``--iters`` timed
calls (fetch-synced), plus a ``verdict`` line per shape comparing pallas
vs the XLA fallback. Flip a kernel's default only on a TPU-backed win.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

try:
    import distkeras_tpu  # noqa: F401  (pip-installed)
except ImportError:  # running from a source checkout: use the repo root
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _time_fn(fn, iters: int) -> float:
    """Median wall time of ``iters`` calls, fetch = completion barrier."""
    np.asarray(fn())  # compile + settle
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        np.asarray(fn())
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def _int8_matmul_cases(shapes):
    """(meta, flops, variants, pallas_fn|None, flag, xla_ref) per
    M,K,N triple."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.ops.pallas import int8_matmul as k

    shapes = shapes or ((512, 512, 512), (1024, 1024, 1024),
                       (2048, 2048, 2048))
    for (m, kk, n), (qx, qw, sxw) in zip(
            shapes, k.reference_rows(sizes=shapes)):
        qxd, qwd = jnp.asarray(qx), jnp.asarray(qw)
        bx = (qxd.astype(jnp.float32) * sxw).astype(jnp.bfloat16)
        bw = qwd.astype(jnp.bfloat16)
        bf16_mm = jax.jit(lambda a, b: (a @ b).astype(jnp.float32))
        xla = jax.jit(k.xla_int8_matmul_dequant)
        variants = {
            "bf16": lambda bx=bx, bw=bw: bf16_mm(bx, bw),
            "xla-int8": lambda a=qxd, b=qwd, s=sxw: xla(a, b, s),
        }
        pallas_fn = None
        if k.fits(qx.shape, qw.shape):
            pallas_fn = lambda a=qxd, b=qwd, s=sxw: k.int8_matmul_dequant(
                a, b, s)
        yield ({"m": m, "k": kk, "n": n}, 2 * m * kk * n, variants,
               pallas_fn, "USE_FUSED_INT8_MATMUL", "xla-int8")


def _flash_attention_cases(shapes):
    """(meta, flops, variants, pallas_fn|None, flag, xla_ref) per
    B,T,H,D shape."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.ops.pallas import flash_attention as k

    shapes = shapes or ((1, 1024, 8, 64), (1, 2048, 12, 64),
                       (2, 4096, 8, 128))
    rng = np.random.default_rng(0)
    for b, t, h, d in shapes:
        qkv = [jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
               for _ in range(3)]
        qkv16 = [x.astype(jnp.bfloat16) for x in qkv]
        xla = jax.jit(lambda q, kk, v: k.reference_attention(
            q, kk, v, causal=True))
        # causal attention: ~half the [T, T] logits are live
        flops, _ = k.modeled_cost((b, t, h, d), causal=True)
        variants = {
            "bf16": lambda a=qkv16: xla(*a),
            "xla-f32": lambda a=qkv: xla(*a),
        }
        pallas_fn = None
        if k.fits((b, t, h, d)):
            pallas_fn = lambda a=qkv16: k.flash_attention(*a, causal=True)
        yield ({"b": b, "t": t, "h": h, "d": d}, flops, variants,
               pallas_fn, "USE_FLASH_ATTENTION", "bf16")


CLIENTS = {
    "int8_matmul": _int8_matmul_cases,
    "flash_attention": _flash_attention_cases,
}


def ablate(kernel: str, shapes=None, iters: int = 5):
    """Yield one timing row per (variant, shape) + a verdict per shape
    (``pallas-wins`` / ``xla-wins`` / ``kernel-declined`` when ``fits()``
    rejects the shape). Raises off-TPU: there the kernel could only run
    interpreted, and an interpreter's clock is not evidence."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"kernel_ablate times compiled Pallas TPU kernels; this "
            f"process runs on {platform!r}")
    for meta, flops, variants, pallas_fn, flag, xla_ref in (
            CLIENTS[kernel](shapes)):
        base = dict(meta, kernel=kernel, backend=platform)
        dts = {name: _time_fn(fn, iters) for name, fn in variants.items()}
        if pallas_fn is not None:
            dts["pallas"] = _time_fn(pallas_fn, iters)
        for variant, dt in dts.items():
            yield dict(base, variant=variant, sec=round(dt, 6),
                       tflops=round(flops / dt / 1e12, 3))
        pallas_dt = dts.get("pallas")
        yield dict(base, verdict=(
            "pallas-wins" if pallas_dt and pallas_dt < dts[xla_ref]
            else "xla-wins" if pallas_dt
            else f"kernel-declined (fits() rejected the shape; keep "
                 f"{flag} off)"))


def parse_shapes(spec):
    """Semicolon-separated comma-tuples -> tuple of int tuples."""
    if not spec:
        return None
    return tuple(tuple(int(v) for v in s.split(","))
                 for s in spec.split(";"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--kernel", choices=sorted(CLIENTS), required=True)
    ap.add_argument("--shapes", default=None,
                    help="semicolon-separated shape tuples — M,K,N for "
                         "int8_matmul, B,T,H,D for flash_attention")
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args()
    for row in ablate(args.kernel, shapes=parse_shapes(args.shapes),
                      iters=args.iters):
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
