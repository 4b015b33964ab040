"""Fused scaled-int8 matmul-dequant Pallas kernel (DESIGN.md §11).

The int8 precision policy's hot path is ``dequant(int8(x) @ int8(w))``:
an int8 x int8 -> int32 MXU dot followed by one f32 multiply by the
product of the per-tensor scales. XLA already lowers the dot to the MXU's
2x-rate int8 path on v5e/v6e, but materializes the int32 accumulator to
HBM before the dequant epilogue; this kernel keeps the accumulator in a
VMEM scratch across the K grid and fuses the dequant into the final
store — one HBM round-trip instead of two.

DEFAULT OFF (``USE_FUSED_INT8_MATMUL = False``), the groupnorm lesson:
a custom call is an optimization FENCE to XLA's fusion pass, and the
groupnorm kernel that ignored that cost the flagship 14 MFU points.
This kernel must beat the pure-XLA int8 fallback end to end in a cell of
``BENCHMARK.json`` (``perf/run.py``, parent against change on the chip)
before the default flips. Until then `precision.py` selects the XLA
fallback at trace time.

Tiling (see /opt/skills/guides: int8 min tile is (32, 128); MXU is
128x128): grid (M/bm, N/bn, K/bk) with ``dimension_semantics =
("parallel", "parallel", "arbitrary")`` so the K reduction stays
sequential while M/N tiles parallelize. Scales ride as (1, 1) SMEM
blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: flip only when a cell of BENCHMARK.json shows the fused kernel beating
#: the XLA int8 dot on the target TPU generation (default-off per the
#: groupnorm precedent — see module docstring)
USE_FUSED_INT8_MATMUL = False

#: block shape: multiples of the int8 min tile (32, 128); 256x256x256
#: int8 blocks + one 256x256 int32 accumulator sit well under the ~16 MB
#: VMEM budget per core
_BM, _BN, _BK = 256, 256, 256


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def kernel_enabled() -> bool:
    """Trace-time dispatch predicate for precision._int8_dot_impl."""
    return USE_FUSED_INT8_MATMUL and _on_tpu()


def fits(x_shape, w_shape) -> bool:
    """The kernel handles the 2-D Dense contraction with block-aligned
    shapes; everything else falls back to XLA. (Padding ragged shapes
    inside the kernel would hide the cost being measured.)"""
    if len(x_shape) != 2 or len(w_shape) != 2:
        return False
    m, k = x_shape
    k2, n = w_shape
    return (k == k2 and m % _BM == 0 and n % _BN == 0 and k % _BK == 0)


def _matmul_kernel(x_ref, w_ref, sxw_ref, o_ref, acc_ref, *, k_steps):
    """One (i, j) output tile: accumulate int8 dot products over the K
    grid in an int32 VMEM scratch, dequantize once on the last K step."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        o_ref[...] = (acc_ref[...].astype(jnp.float32)
                      * sxw_ref[0, 0]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def int8_matmul_dequant(qx, qw, sxw, interpret: bool = False):
    """``(qx int8 [M,K]) @ (qw int8 [K,N]) * sxw -> f32 [M,N]`` with the
    int32 accumulator resident in VMEM. ``sxw`` is the product of the two
    per-tensor scales (f32 scalar). ``interpret=True`` runs the kernel on
    CPU for tests."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = qx.shape
    _, n = qw.shape
    k_steps = k // _BK
    grid = (m // _BM, n // _BN, k_steps)
    sxw = jnp.asarray(sxw, jnp.float32).reshape(1, 1)
    kwargs = {}
    if not interpret:
        # K must stay sequential (the accumulator carries across it);
        # M/N tiles are free to parallelize
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_BM, _BK), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((_BK, _BN), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((_BM, _BN), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((_BM, _BN), jnp.int32)],
        interpret=interpret,
        **kwargs,
    )(qx, qw, sxw)


def xla_int8_matmul_dequant(qx, qw, sxw):
    """The pure-XLA fallback the kernel must beat: same math, XLA's own
    fusion of the dequant epilogue."""
    acc = jax.lax.dot_general(qx, qw, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * jnp.asarray(sxw, jnp.float32)


def reference_rows(sizes=((512, 512, 512),), seed=0):
    """Deterministic test/ablation inputs: (qx, qw, sxw) per (m, k, n)."""
    rng = np.random.default_rng(seed)
    out = []
    for m, k, n in sizes:
        qx = rng.integers(-127, 128, (m, k)).astype(np.int8)
        qw = rng.integers(-127, 128, (k, n)).astype(np.int8)
        out.append((qx, qw, np.float32(rng.uniform(1e-4, 1e-2))))
    return out
