"""Fused flash-style attention Pallas kernels (DESIGN.md §23).

Two kernels share one skeleton — a grid whose last dimension walks key
blocks while per-query-block statistics live in VMEM scratch:

- :func:`flash_attention` — the training kernel. Online-softmax tiling
  (running max ``m``, running denominator ``l``, rescaled accumulator)
  over ``block_q x block_k`` tiles, causal-mask-aware tile skipping
  (tiles whose every key position exceeds every query position are
  predicated off — ~half the FLOPs at causal shapes), and a
  ``custom_vjp`` backward that RECOMPUTES the probability tiles from
  (q, k, lse) instead of storing the [T, T] matrix: two more pallas
  kernels (dq; dk/dv) gridded the same way. O(T) HBM traffic where the
  XLA path materializes O(T^2) logits.

- :func:`paged_flash_attention` — the decode kernel (ROADMAP item 2a).
  The grid's key-block axis walks the PAGE TABLE: each step's BlockSpec
  index map reads ``page_table[b, j]`` (scalar prefetch) so the DMA
  engine fetches ``pages[page_table[b, j]]`` directly — the dense
  ``[batch, max_len, heads, head_dim]`` HBM view the XLA path gathers
  (DESIGN.md §19's honest limit) is never materialized. Pages stream
  into a VMEM staging buffer and the final step runs the IDENTICAL
  fixed-contraction-length masked softmax as the reference, so paged
  decode logits stay BITWISE-equal to the rectangular path
  (tests/test_paged_generation.py's oracle) — this kernel deliberately
  does NOT use online softmax: reassociating the denominator would
  trade the repo's decode-exactness contract for a VMEM saving
  (NUMERICS.md "Flash-attention equivalence").

DEFAULT OFF (``USE_FLASH_ATTENTION = False``), the groupnorm lesson
(DESIGN.md §6): a custom call is a fusion FENCE to XLA, and this kernel
must beat the XLA attention end to end in a cell of ``BENCHMARK.json``
(``perf/run.py``, parent against change on the chip) before the default
flips. Until then ``attention="flash"``
reaches the upstream pallas kernel and the paged branch takes the XLA
gather. Tests force the kernels through ``interpret=True`` on CPU
(forward/backward ulp-parity for the training kernel; bitwise parity for
the paged kernel); ``chip_smoke.py`` compiles both WITHOUT interpret on
the chip and compares them with their XLA references.

Tiling (see /opt/skills/guides: f32 min tile (8, 128), bf16 (16, 128),
MXU 128x128): default 128x128 tiles; head_dim rides the lane dimension
(padded below 128 — honest cost for small heads, stated by ``fits``).
Every block's last two dims are multiples of the tile or the full array
dims: the per-row statistics (lse, delta) are ``[b, h, 1, t]`` lane-major
rows, transposed to and from the kernels' sublane-column form in VMEM.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.ops.attention import MASK_VALUE

#: flip only when a cell of BENCHMARK.json shows the fused kernel beating
#: the XLA attention on the target TPU generation (default-off per the
#: groupnorm precedent)
USE_FLASH_ATTENTION = False

#: test hook: dispatch the PAGED kernel in interpret mode off-TPU so the
#: full gpt decode path can be driven through it on CPU (the bitwise
#: oracle in tests/test_flash_attention.py); never set in production
PAGED_INTERPRET = False

#: opt-in int8-KV kernel stepping stone (DESIGN.md §19, ISSUE 20):
#: when on AND the f32 shapes fit, the int8 paged step dequantizes the
#: page POOL and runs the fused paged kernel over it instead of the XLA
#: gather path. Default OFF per the groupnorm lesson — it reads
#: round-tripped in-call values and wins nothing until the dequant moves
#: inside the kernel grid; flip only behind a kernel_ablate.py receipt.
PAGED_INT8_KERNEL = False

#: default tile sizes — one MXU tile per dot
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128

#: Mosaic's scoped-VMEM limit on v5e is 16 MiB (measured: the compiler
#: rejects a 16.02 MiB kernel); stay under it with headroom
_VMEM_BUDGET_BYTES = 14 * 1024 * 1024

#: per-row softmax statistics are replicated across one lane tile so
#: stores stay (sublane, lane)-shaped
_STATS_LANES = 128


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def kernel_enabled() -> bool:
    """Trace-time dispatch predicate for the attention resolve switch."""
    return USE_FLASH_ATTENTION and _on_tpu()


def fits(q_shape, block_q: int = DEFAULT_BLOCK_Q,
         block_k: int = DEFAULT_BLOCK_K) -> bool:
    """The training kernel handles [batch, seq, heads, head_dim] with the
    sequence block-aligned and the head riding the lane dim; it declines
    everything else (padding ragged sequences inside the kernel would
    hide the cost being measured)."""
    if len(q_shape) != 4:
        return False
    _, t, _, d = q_shape
    if t < block_q or t % block_q or t % block_k:
        return False
    # head_dim is the lane dimension of every block: one lane tile max,
    # sublane-aligned so the f32 scratch tiles stay legal
    return 8 <= d <= 128 and d % 8 == 0


def _tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of one ``[rows, cols]`` minor pair: Mosaic pads it to
    whole (sublane, lane) tiles — (8, 128) at 32 bits, (16, 128) at 16."""
    sublanes = 8 * (4 // itemsize)
    return (-(-rows // sublanes) * sublanes
            * -(-cols // 128) * 128 * itemsize)


def paged_fits(q_shape, pages_shape, page_table_shape, dtype) -> bool:
    """The paged kernel stages one row's K/V view in VMEM; decline when
    that staging buffer (plus the page, q and out blocks and the
    ``[t, max_len]`` f32 softmax temporaries) would not fit. Every
    position of the view is a ``[heads, head_dim]`` minor pair, counted
    at its PADDED tile size — at GPT-2-small's (12, 64) that is 2.7x
    the unpadded bytes."""
    if len(q_shape) != 4 or len(pages_shape) != 4:
        return False
    b, t, h, d = q_shape
    _, ps, hp, dp = pages_shape
    if (h, d) != (hp, dp):
        return False
    max_len = page_table_shape[1] * ps
    itemsize = np.dtype(dtype).itemsize
    cell = _tile_bytes(h, d, itemsize)
    staging = 2 * max_len * cell                           # k_view + v_view
    pages = 2 * 2 * ps * cell                              # double-buffered
    q_out = 2 * 2 * h * _tile_bytes(t, d, itemsize)
    softmax = 4 * _tile_bytes(t, max_len, 4)   # logits, mask, exp, weights
    return staging + pages + q_out + softmax <= _VMEM_BUDGET_BYTES


# -- training kernel: forward ------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *,
                scale, block_q, block_k, num_k_blocks, causal):
    """One (batch, head, q-block) strip: the k-block grid axis is
    sequential, carrying (m, l, acc) in VMEM scratch."""
    from jax.experimental import pallas as pl

    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal tile skipping: a tile is live iff its SMALLEST key position
    # is visible to its LARGEST query position; fully-masked tiles skip
    # both dots (the diagonal tile still masks elementwise below)
    live = (ik * block_k <= iq * block_q + block_q - 1) if causal \
        else (ik >= 0)

    @pl.when(live)
    def _tile():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [bq, bk]
        if causal:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            # same finite MASK_VALUE as the XLA path: masked entries
            # underflow to exact-zero probability, never NaN
            s = jnp.where(q_pos >= k_pos, s, MASK_VALUE)
        m_prev = m_ref[...]                                 # [bq, 128]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)           # [bq, 1]
        m_next = jnp.maximum(m_prev, m_cur)                 # replicated
        alpha = jnp.exp(m_prev - m_next)                    # rescale old
        p = jnp.exp(s - m_next[:, :1])                      # [bq, bk]
        m_ref[...] = m_next
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0, :, :],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bq, d]
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv

    @pl.when(ik == num_k_blocks - 1)
    def _finish():
        o_ref[0, 0, :, :] = (acc_ref[...]
                             / l_ref[:, :1]).astype(o_ref.dtype)
        # the statistics are lane-replicated sublane columns; the lse
        # row is stored lane-major ([1, bq]), so transpose the replicated
        # tile and keep one sublane
        lse = m_ref[...] + jnp.log(l_ref[...])              # [bq, 128]
        lse_ref[0, 0, :, :] = lse.T[:1, :]


def _fwd_impl(q, k, v, causal, block_q, block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, h, d = q.shape
    qt, kt, vt = (x.swapaxes(1, 2) for x in (q, k, v))      # [b, h, t, d]
    nq, nk = t // block_q, t // block_k
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"))
    o, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=d ** -0.5, block_q=block_q,
            block_k=block_k, num_k_blocks=nk, causal=causal),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda ib, ih, iq, ik: (ib, ih, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda ib, ih, iq, ik: (ib, ih, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda ib, ih, iq, ik: (ib, ih, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _STATS_LANES), jnp.float32),
            pltpu.VMEM((block_q, _STATS_LANES), jnp.float32),
        ],
        interpret=interpret,
        **kwargs,
    )(qt, kt, vt)
    return o.swapaxes(1, 2), lse


# -- training kernel: backward (recomputed tiles) ----------------------------

def _row_to_col(row_ref):
    """A ``(1, 1, 1, bq)`` lane-major statistics block as a ``[bq, 1]``
    sublane column (the orientation that broadcasts against a
    ``[bq, bk]`` score tile): replicate over one lane tile of sublanes,
    transpose, keep one lane."""
    row = row_ref[0, 0, :, :]                               # [1, bq]
    return jnp.broadcast_to(row, (_STATS_LANES, row.shape[1])).T[:, :1]


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, acc_ref, *,
                   scale, block_q, block_k, num_k_blocks, causal):
    from jax.experimental import pallas as pl

    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = (ik * block_k <= iq * block_q + block_q - 1) if causal \
        else (ik >= 0)

    @pl.when(live)
    def _tile():
        q = q_ref[0, 0, :, :].astype(jnp.float32)
        k = k_ref[0, 0, :, :].astype(jnp.float32)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        do = do_ref[0, 0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, MASK_VALUE)
        # recompute the probability tile from the saved log-sum-exp:
        # masked entries underflow to exact zero, so they shed no grad
        p = jnp.exp(s - _row_to_col(lse_ref))               # [bq, bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bq, bk]
        ds = p * (dp - _row_to_col(delta_ref))
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bq, d]

    @pl.when(ik == num_k_blocks - 1)
    def _finish():
        dq_ref[0, 0, :, :] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *,
                    scale, block_q, block_k, num_q_blocks, causal):
    """Transposed strip: one (batch, head, k-block), walking q blocks."""
    from jax.experimental import pallas as pl

    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    live = (ik * block_k <= iq * block_q + block_q - 1) if causal \
        else (iq >= 0)

    @pl.when(live)
    def _tile():
        q = q_ref[0, 0, :, :].astype(jnp.float32)
        k = k_ref[0, 0, :, :].astype(jnp.float32)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        do = do_ref[0, 0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, MASK_VALUE)
        p = jnp.exp(s - _row_to_col(lse_ref))               # [bq, bk]
        dv_acc_ref[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - _row_to_col(delta_ref))
        dk_acc_ref[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bk, d]

    @pl.when(iq == num_q_blocks - 1)
    def _finish():
        dk_ref[0, 0, :, :] = (dk_acc_ref[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc_ref[...].astype(dv_ref.dtype)


def _bwd_impl(q, k, v, o, lse, do, causal, block_q, block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, h, d = q.shape
    qt, kt, vt, ot, dot_ = (x.swapaxes(1, 2) for x in (q, k, v, o, do))
    # delta[b,h,i] = sum_d do*o — the rowwise correction term; cheap
    # elementwise work XLA fuses fine, so it stays outside the kernels
    delta = jnp.sum(dot_.astype(jnp.float32) * ot.astype(jnp.float32),
                    axis=-1)[:, :, None, :]                 # [b, h, 1, t]
    nq, nk = t // block_q, t // block_k
    scale = d ** -0.5
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"))

    q_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda ib, ih, i, j: (ib, ih, i, 0))
    k_spec = pl.BlockSpec((1, 1, block_k, d),
                          lambda ib, ih, i, j: (ib, ih, j, 0))
    row_spec = pl.BlockSpec((1, 1, 1, block_q),
                            lambda ib, ih, i, j: (ib, ih, 0, i))
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, block_q=block_q,
            block_k=block_k, num_k_blocks=nk, causal=causal),
        grid=(b, h, nq, nk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret, **kwargs,
    )(qt, kt, vt, dot_, lse, delta)

    # transposed grid: (b, h, k-block, q-block), q sequential
    qT_spec = pl.BlockSpec((1, 1, block_q, d),
                           lambda ib, ih, j, i: (ib, ih, i, 0))
    kT_spec = pl.BlockSpec((1, 1, block_k, d),
                           lambda ib, ih, j, i: (ib, ih, j, 0))
    rowT_spec = pl.BlockSpec((1, 1, 1, block_q),
                             lambda ib, ih, j, i: (ib, ih, 0, i))
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, block_q=block_q,
            block_k=block_k, num_q_blocks=nq, causal=causal),
        grid=(b, h, nk, nq),
        in_specs=[qT_spec, kT_spec, kT_spec, qT_spec, rowT_spec,
                  rowT_spec],
        out_specs=[kT_spec, kT_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, t, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h, t, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret, **kwargs,
    )(qt, kt, vt, dot_, lse, delta)
    return (dq.swapaxes(1, 2), dk.swapaxes(1, 2), dv.swapaxes(1, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    o, _ = _fwd_impl(q, k, v, causal, block_q, block_k, interpret)
    return o


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    o, lse = _fwd_impl(q, k, v, causal, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    return _bwd_impl(q, k, v, o, lse, do, causal, block_q, block_k,
                     interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = True,
                    block_q: int = None, block_k: int = None,
                    interpret: bool = False):
    """Fused attention over ``[batch, seq, heads, head_dim]`` tensors.

    Differentiable (``custom_vjp``; backward recomputes probability
    tiles). Callers should gate on :func:`kernel_enabled` and
    :func:`fits` — this function asserts ``fits`` rather than silently
    padding. ``interpret=True`` runs on CPU for tests.
    """
    block_q = block_q or min(DEFAULT_BLOCK_Q, q.shape[1])
    block_k = block_k or min(DEFAULT_BLOCK_K, q.shape[1])
    if not fits(q.shape, block_q, block_k):
        raise ValueError(
            f"flash_attention fits() rejected shape {q.shape} at blocks "
            f"({block_q}, {block_k})")
    return _flash(q, k, v, causal, block_q, block_k, interpret)


# -- paged decode kernel (ROADMAP item 2a) -----------------------------------

def _paged_kernel(pt_ref, ci_ref, q_ref, kp_ref, vp_ref, o_ref,
                  kview_ref, vview_ref, *,
                  page_size, pages_per_row, block_t, num_heads, scale):
    """Grid (batch, page-slot). Step j DMAs ``pages[page_table[b, j]]``
    (the BlockSpec index map reads the prefetched table) into the VMEM
    staging view; the last step runs the reference's exact
    fixed-contraction-length masked softmax over it."""
    from jax.experimental import pallas as pl

    ib = pl.program_id(0)
    j = pl.program_id(1)
    kview_ref[pl.ds(j * page_size, page_size)] = kp_ref[0]
    vview_ref[pl.ds(j * page_size, page_size)] = vp_ref[0]

    @pl.when(j == pages_per_row - 1)
    def _attend():
        max_len = pages_per_row * page_size
        dtype = q_ref.dtype
        # positions of this call's query block; keys visible iff
        # key_pos <= pos (identical mask to the rectangular path)
        pos = ci_ref[ib] + jax.lax.broadcasted_iota(
            jnp.int32, (block_t, max_len), 0)
        key_pos = jax.lax.broadcasted_iota(
            jnp.int32, (block_t, max_len), 1)
        mask = key_pos <= pos
        for hh in range(num_heads):  # static unroll: rank-2 MXU dots
            qh = q_ref[0, hh]                              # [t, d]
            kh = kview_ref[:, hh, :]                       # [max_len, d]
            vh = vview_ref[:, hh, :]
            logits = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32
            ).astype(dtype).astype(jnp.float32) * scale    # [t, max_len]
            logits = jnp.where(mask, logits, MASK_VALUE)
            w = jax.nn.softmax(logits, axis=-1).astype(dtype)
            o_ref[0, hh] = jax.lax.dot_general(
                w, vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(dtype)


def paged_flash_attention(q, k_pages, v_pages, page_table, cache_index,
                          interpret: bool = False):
    """Decode attention over a paged KV pool, ``pages[page_table]``
    indexed inside the kernel loop.

    ``q``: [batch, t, heads, head_dim] (the in-call block, ALREADY
    scattered into the pages by the caller); ``k_pages``/``v_pages``:
    [num_pages + 1, page_size, heads, head_dim]; ``page_table``:
    [batch, pages_per_row] int32; ``cache_index``: [batch] int32.
    Returns [batch, t, heads, head_dim], bitwise-equal (f32) to the
    dense-gather path at every unmasked position.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, h, d = q.shape
    ps = k_pages.shape[1]
    pmax = page_table.shape[1]
    max_len = pmax * ps
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pmax),
        in_specs=[
            pl.BlockSpec((1, h, t, d),
                         lambda ib, j, pt, ci: (ib, 0, 0, 0)),
            pl.BlockSpec((1, ps, h, d),
                         lambda ib, j, pt, ci: (pt[ib, j], 0, 0, 0)),
            pl.BlockSpec((1, ps, h, d),
                         lambda ib, j, pt, ci: (pt[ib, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, t, d),
                               lambda ib, j, pt, ci: (ib, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((max_len, h, d), k_pages.dtype),
            pltpu.VMEM((max_len, h, d), v_pages.dtype),
        ],
    )
    # heads lead inside the kernel so each head's [t, d] query and
    # output tile is a leading-dim index, not a sublane gather
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, page_size=ps, pages_per_row=pmax,
            block_t=t, num_heads=h, scale=d ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), cache_index.astype(jnp.int32),
      q.swapaxes(1, 2), k_pages, v_pages)
    return out.swapaxes(1, 2)


def paged_dispatch(q_shape, pages_shape, page_table_shape, dtype) -> bool:
    """Trace-time predicate for the gpt paged branch: kernel on (TPU
    ablation flag, or the interpret test hook) AND the shapes fit."""
    if not (kernel_enabled() or PAGED_INTERPRET):
        return False
    return paged_fits(q_shape, pages_shape, page_table_shape, dtype)


# -- references + cost model -------------------------------------------------

def reference_attention(q, k, v, causal: bool = True):
    """The masked-softmax XLA reference both kernels are judged against
    (same math as ops.attention.dot_product_attention)."""
    from distkeras_tpu.ops.attention import dot_product_attention

    return dot_product_attention(q, k, v, causal=causal)


def modeled_cost(q_shape, dtype_bytes: int = 2, causal: bool = True):
    """Roofline (flops, hbm_bytes) for the FUSED forward at one shape —
    the kernel-modeled row the op-attribution evidence substitutes for
    the XLA attention group. FLOPs match the XLA path (the fusion saves
    traffic, not math; causal tile skipping halves both); bytes are one
    pass over q/k/v/o plus the lse row — the [T, T] logits never reach
    HBM."""
    b, t, h, d = q_shape
    frac = 0.5 if causal else 1.0
    flops = frac * (2 * b * h * t * t * d        # q @ k^T
                    + 2 * b * h * t * t * d      # p @ v
                    + 5 * b * h * t * t)         # mask+softmax elementwise
    bytes_accessed = (4 * b * t * h * d * dtype_bytes   # q, k, v, o
                      + b * h * t * 4)                  # lse (f32)
    return flops, bytes_accessed


def modeled_train_cost(q_shape, dtype_bytes: int = 2, causal: bool = True):
    """(flops, hbm_bytes) for forward PLUS the recompute backward — the
    currency the op-attribution evidence substitutes for the whole
    attention group of a grad step. The backward recomputes s/p from
    saved lse instead of reading a stored [T, T] probability matrix, so
    it costs ~2.5x the forward's matmul FLOPs (qk^T again, dp, ds
    contractions, dv, dk) but its HBM traffic stays linear in T: reads
    q/k/v/o/do, writes dq/dk/dv, plus the f32 lse/delta rows."""
    b, t, h, d = q_shape
    fwd_flops, fwd_bytes = modeled_cost(q_shape, dtype_bytes, causal)
    frac = 0.5 if causal else 1.0
    # bwd matmuls: recomputed q@k^T, dp = do@v^T, dq += ds@k,
    # dv += p^T@do, dk += ds^T@q — five T*T*d contractions vs fwd's two,
    # plus the recomputed softmax elementwise
    bwd_flops = frac * (5 * 2 * b * h * t * t * d + 5 * b * h * t * t)
    bwd_bytes = (8 * b * t * h * d * dtype_bytes   # q,k,v,o,do + dq,dk,dv
                 + 2 * b * h * t * 4)              # lse + delta rows (f32)
    return fwd_flops + bwd_flops, fwd_bytes + bwd_bytes


def xla_modeled_cost(q_shape, dtype_bytes: int = 2, causal: bool = True):
    """Same currency for the XLA path: identical FLOPs, but the [T, T]
    logits + probability matrices round-trip HBM (written by the first
    matmul fusion, re-read by softmax, re-written, re-read by the second
    matmul — 2 writes + 2 reads of b*h*t*t at f32)."""
    flops, bytes_accessed = modeled_cost(q_shape, dtype_bytes, causal)
    b, t, h, d = q_shape
    return flops, bytes_accessed + 4 * b * h * t * t * 4
