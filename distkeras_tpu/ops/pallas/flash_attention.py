"""Fused flash-style attention Pallas kernels (DESIGN.md §23).

- :func:`flash_attention` — the training kernel, and what ``models/gpt.py``
  attends a causal call with no cache with on a TPU (:func:`train_dispatch`:
  the backend, a call on one device as traced, and :func:`fits` for the
  shape; no flag). It reads Q, K and
  V as the qkv projection lays them out, ``[batch, t, heads * head_dim]``,
  in lane blocks of as many heads as fill 128 lanes (:func:`_group`), so
  no ``[b, h, t, d]`` copy is made. A grid step is one (batch, lane
  block), its arrays in VMEM whole; the tiles it runs are known from the
  shape, so their loops unroll at trace time. The forward walks, for each
  q-block, only the key blocks it sees, with an online softmax (running
  max ``m``, running sum ``l``, rescaled float32 accumulator) and the
  causal mask on the blocks across the diagonal alone; K and V are
  "spread" (:func:`_spread`) so that one product gives the block's heads'
  scores side by side at the MXU's full depth. A ``custom_vjp`` backward
  RECOMPUTES the probability tiles from (q, k, lse) instead of storing the
  ``[T, T]`` matrix: one kernel adds into dQ, dK and dV at once, five
  products a tile. O(T) HBM traffic where the XLA path materializes O(T^2)
  logits, three times a step under ``remat``.

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

``USE_FLASH_ATTENTION`` (default off) still gates the in-repo kernel
under ``attention="flash"`` (``ops/attention.apply_attention``), which
otherwise reaches the upstream Pallas kernel; ``PAGED_INTERPRET`` and
``PAGED_INT8_KERNEL`` gate the paged branch. Tests force the kernels
through ``interpret=True`` on CPU (forward/backward ulp-parity for the
training kernel; bitwise parity for the paged kernel); ``chip_smoke.py``
compiles both WITHOUT interpret on the chip and compares them with their
XLA references.

Tiling (see /opt/skills/guides: f32 min tile (8, 128), bf16 (16, 128),
MXU 128x128): blocks of queries and keys follow from the sequence
(:func:`_tiles`); head_dim rides the lane dimension (padded below 128 —
honest cost for small heads, stated by ``fits``). Every block's last two
dims are multiples of the tile or the full array dims: the per-row
statistics (lse, delta) are ``[b, h, 1, t]`` lane-major rows, transposed
to and from the kernels' sublane-column form in VMEM. The numbers are
``dot_product_attention``'s up to the order of the float32 sums: float32
logits, softmax statistics and accumulators, probabilities cast to the
inputs' dtype before P.V (and, in the backward, P and dS before their
products), products of the inputs' dtype accumulated in float32.
"""

from __future__ import annotations

import functools

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

#: the largest block of queries or keys the training kernel's tiles take
#: (measured at gpt2-medium's attention on a v5e: the forward 0.34 / 0.35 /
#: 0.40 / 0.47 ms at 256 / 512 / 1024 / 128, the backward 0.66 / 0.68 /
#: 0.87 ms at 256 / 512 / 128; PERF.md §6); the VMEM budget may make it
#: smaller
_MAX_BLOCK = 256

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


# -- training kernel ---------------------------------------------------------

def _group(num_heads: int, head_dim: int):
    """Heads side by side in one block of lanes: as many as fill 128 lanes
    (all of them where the heads together are narrower), or None where
    ``head_dim`` does not divide 128 lanes or that many heads do not divide
    ``num_heads``."""
    if not (8 <= head_dim <= 128 and 128 % head_dim == 0):
        return None
    group = min(128 // head_dim, num_heads)
    return group if num_heads % group == 0 else None


def _vmem_bytes(t: int, lanes: int, group: int, itemsize: int,
                block_q: int, block_k: int) -> int:
    """VMEM the backward kernel asks for: a lane block's Q, K, V, dO, dQ,
    dK and dV whole and double-buffered, the float32 dQ, the statistics
    rows and their column copies, the float32 dK and dV sums of a key
    block, and four ``[block_q, group * block_k]`` float32
    temporaries."""
    rows = (2 * 2 * group * _tile_bytes(1, t, 4)
            + 2 * group * _tile_bytes(t, _STATS_LANES, 4))
    return (7 * 2 * _tile_bytes(t, lanes, itemsize)
            + _tile_bytes(t, lanes, 4) + rows
            + 2 * _tile_bytes(group * block_k, lanes, 4)
            + 4 * _tile_bytes(block_q, group * block_k, 4))


def _fwd_vmem_bytes(t: int, lanes: int, group: int, itemsize: int,
                    block_q: int, block_k: int) -> int:
    """VMEM the forward kernel asks for: a lane block's Q, K, V and O whole
    and double-buffered, its log-sum-exp rows, and two ``[block_q, group
    * block_k]`` float32 temporaries (scores and probabilities)."""
    return (4 * 2 * _tile_bytes(t, lanes, itemsize)
            + 2 * group * _tile_bytes(1, t, 4)
            + 2 * _tile_bytes(block_q, group * block_k, 4))


def _largest_block(t: int, largest: int, fits_vmem) -> int:
    """The largest power of two times 128, at most ``largest``, that
    divides ``t`` and that ``fits_vmem``; None where none does."""
    block = largest
    while block >= 128:
        if t % block == 0 and fits_vmem(block):
            return block
        block //= 2
    return None


def _tiles(q_shape, itemsize: int):
    """``((block_q, block_k) of the forward, (block_q, block_k) of the
    backward)`` for ``[b, t, h, d]`` queries (:func:`_largest_block` of
    each kernel's VMEM), or None where the heads do not group
    (:func:`_group`) or no block fits."""
    _, t, h, d = q_shape
    group = _group(h, d)
    if group is None:
        return None
    lanes = group * d
    fwd = _largest_block(t, _MAX_BLOCK, lambda block: _fwd_vmem_bytes(
        t, lanes, group, itemsize, block, block) <= _VMEM_BUDGET_BYTES)
    bwd = _largest_block(t, _MAX_BLOCK, lambda block: _vmem_bytes(
        t, lanes, group, itemsize, block, block) <= _VMEM_BUDGET_BYTES)
    if fwd is None or bwd is None:
        return None
    return (fwd, fwd), (bwd, bwd)


def _blocks(block_q, block_k):
    """The blocks a caller names: both or neither."""
    if (block_q is None) != (block_k is None):
        raise ValueError(
            f"name both blocks or neither: block_q={block_q}, "
            f"block_k={block_k}")
    return None if block_q is None else (block_q, block_k)


def fits(q_shape, dtype, block_q: int = None, block_k: int = None) -> bool:
    """The training kernel takes ``[batch, seq, heads, head_dim]`` queries
    of ``dtype`` whose heads group into lane blocks (:func:`_group`) and
    whose sequence is a whole number of blocks, with a lane block's whole
    sequence in VMEM: the blocks named here (both, serving the forward and
    the backward alike) or else :func:`_tiles`'. It declines everything
    else (padding ragged sequences inside the kernel would hide the cost
    being measured). A lane block's sequence is resident, so long
    sequences do not fit (at 64-wide heads, past 2048 positions in
    bfloat16 and 1024 in float32): they keep the O(t^2) XLA path."""
    blocks = _blocks(block_q, block_k)
    if len(q_shape) != 4:
        return False
    _, t, h, d = q_shape
    group = _group(h, d)
    if group is None:
        return False
    itemsize = np.dtype(dtype).itemsize
    if blocks is None:
        return _tiles(q_shape, itemsize) is not None
    lanes = group * d
    return (t >= block_q and t % block_q == 0 and t % block_k == 0
            and _fwd_vmem_bytes(t, lanes, group, itemsize, block_q,
                                block_k) <= _VMEM_BUDGET_BYTES
            and _vmem_bytes(t, lanes, group, itemsize, block_q,
                            block_k) <= _VMEM_BUDGET_BYTES)


def _causal_mask(s, q_start, k_start, group):
    """``s`` (``group`` heads' ``[rows, keys]`` side by side) with key
    positions past the query's at ``MASK_VALUE``, the XLA path's finite
    value: masked entries underflow to exact-zero probability, never
    NaN."""
    rows, keys = s.shape[0], s.shape[1] // group
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 1)
    return jnp.where(jnp.tile(q_pos >= k_pos, (1, group)), s, MASK_VALUE)


def _as_column(row):
    """A ``[1, n]`` lane-major statistics row as ``[n, 128]`` sublane
    columns (each row's value replicated over one lane tile), the
    orientation that broadcasts against a ``[rows, keys]`` score tile."""
    return jnp.broadcast_to(row, (_STATS_LANES, row.shape[1])).T


def _lanes(stats, n: int):
    """Lane-replicated ``[rows, 128]`` statistics as ``[rows, n]``: the
    same vregs repeated (or a part of one), where a ``[rows, 1]`` column
    would be broadcast across the lanes of every tile."""
    if n <= _STATS_LANES:
        return stats[:, :n]
    return jnp.tile(stats, (1, n // _STATS_LANES))


def _head_of_lane(shape, head_dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1) // head_dim


def _spread(x, group, head_dim):
    """``[n, lanes]`` rows of ``group`` heads as ``[group * n, lanes]``:
    row block ``j`` keeps head ``j``'s lanes and zeros elsewhere. Against
    it one product gives every head's scores side by side, the MXU's full
    depth in use where a 64-wide head alone would fill half of it; the
    zeros add nothing to the float32 sums."""
    if group == 1:
        return x
    head = _head_of_lane(x.shape, head_dim)
    return jnp.concatenate(
        [jnp.where(head == j, x, jnp.zeros_like(x)) for j in range(group)],
        axis=0)


def _gather_heads(x, group, head_dim, n):
    """The inverse of :func:`_spread` for a ``[group * n, lanes]`` sum:
    each head's lanes from its own row block."""
    if group == 1:
        return x
    head = _head_of_lane((n, x.shape[1]), head_dim)
    out = jnp.zeros((n, x.shape[1]), x.dtype)
    for j in range(group):
        out = jnp.where(head == j, x[j * n:(j + 1) * n], out)
    return out


def _by_lane(stats, group, head_dim, lanes):
    """Per-head lane-replicated ``[rows, 128]`` statistics as one
    ``[rows, lanes]`` array, each lane its head's."""
    out = stats[0][:, :lanes]
    if group > 1:
        head = _head_of_lane(out.shape, head_dim)
        for j in range(1, group):
            out = jnp.where(head == j, stats[j][:, :lanes], out)
    return out


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                scale, block_q, block_k, causal, group, head_dim):
    """One (batch, lane block of ``group`` heads), its Q, K, V and output
    in VMEM whole. For each q-block, the key blocks it sees, in a loop
    unrolled at trace time (the blocks are known from the shape, so the
    scheduler overlaps one tile's products with the last one's softmax),
    carrying each head's running maximum ``m`` and sum ``l``
    (lane-replicated ``[bq, 128]``) and the weighted values in float32.
    Fully masked key blocks are never visited; only the blocks across the
    diagonal are masked."""
    t = k_ref.shape[1]
    lanes = q_ref.shape[2]
    d = head_dim

    def tile(q, q_start, start, carry, masked):
        m, l, acc = carry
        k = _spread(k_ref[0, start:start + block_k, :], group, d)
        v = _spread(v_ref[0, start:start + block_k, :], group, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [bq, g*bk]
        if masked:
            s = _causal_mask(s, q_start, start, group)
        heads = [slice(h * block_k, (h + 1) * block_k) for h in range(group)]
        m_next = tuple(jnp.maximum(m[h], jnp.max(s[:, cols], axis=1,
                                                 keepdims=True))
                       for h, cols in enumerate(heads))
        alpha = tuple(jnp.exp(m[h] - m_next[h]) for h in range(group))
        p = jnp.exp(s - jnp.concatenate(
            [_lanes(mh, block_k) for mh in m_next], axis=1))
        l = tuple(alpha[h] * l[h] + jnp.sum(p[:, cols], axis=1,
                                            keepdims=True)
                  for h, cols in enumerate(heads))
        acc = _by_lane(alpha, group, d, lanes) * acc + \
            jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # [bq, lanes]
        return m_next, l, acc

    stats = lambda value: tuple(
        jnp.full((block_q, _STATS_LANES), value, jnp.float32)
        for _ in range(group))
    for iq in range(t // block_q):
        q_start = iq * block_q
        q = q_ref[0, q_start:q_start + block_q, :]
        carry = (stats(MASK_VALUE), stats(0.0),
                 jnp.zeros((block_q, lanes), jnp.float32))
        if causal:
            # key blocks wholly at or before the first query: no mask;
            # then the blocks across the diagonal, up to the last query's
            full = (q_start + 1) // block_k
            live = (q_start + block_q - 1) // block_k + 1
        else:
            full = live = t // block_k
        for j in range(live):
            carry = tile(q, q_start, j * block_k, carry, j >= full)
        m, l, acc = carry
        o_ref[0, q_start:q_start + block_q, :] = (
            acc / _by_lane(l, group, d, lanes)).astype(o_ref.dtype)
        for h in range(group):
            lse_ref[0, h, :, q_start:q_start + block_q] = (
                m[h] + jnp.log(l[h])).T[:1, :]


def _lane_blocks(x):
    """``[b, t, h, d]`` as ``[b, t, h * d]``: the same bytes, heads side
    by side on the lanes, as the qkv projection emits them."""
    return x.reshape(x.shape[:2] + (-1,))


def _varying(*arrays):
    """The mesh axes over which any of ``arrays`` varies inside a
    ``shard_map`` (none outside one): what the kernels' results vary
    over, which a ``shard_map`` that checks it asks them to say."""
    return frozenset().union(*(jax.typeof(x).vma for x in arrays))


def _fwd_impl(q, k, v, causal, block_q, block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, h, d = q.shape
    group = _group(h, d)
    lanes = group * d
    vma = _varying(q, k, v)
    whole = pl.BlockSpec((1, t, lanes), lambda ib, ig: (ib, 0, ig))
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=d ** -0.5, block_q=block_q,
                          block_k=block_k, causal=causal, group=group,
                          head_dim=d),
        grid=(b, h // group),
        in_specs=[whole, whole, whole],
        out_specs=[whole,
                   pl.BlockSpec((1, group, 1, t),
                                lambda ib, ig: (ib, ig, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, t, h * d), q.dtype, vma=vma),
                   jax.ShapeDtypeStruct((b, h, 1, t), jnp.float32,
                                        vma=vma)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="flash_attention_fwd",
        interpret=interpret,
    )(_lane_blocks(q), _lane_blocks(k), _lane_blocks(v))
    return o.reshape(q.shape), lse


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, lse_col, delta_col, *,
                scale, block_q, block_k, causal, group, head_dim):
    """One (batch, lane block), all three gradients at once, every array
    in VMEM whole and the loops unrolled at trace time: for each key
    block, the q-blocks that see it; each probability tile recomputed from
    (q, k, lse) and added into the key block's dK and dV and into the lane
    block's float32 dQ. Five products a tile, where separate dQ and dK/dV
    kernels recompute two of them."""
    t = q_ref.shape[1]
    dq_acc[...] = jnp.zeros_like(dq_acc)
    for h in range(group):
        lse_col[h] = _as_column(lse_ref[0, h, :, :])
        delta_col[h] = _as_column(delta_ref[0, h, :, :])
    num_q_blocks = t // block_q
    for ik in range(t // block_k):
        k_start = ik * block_k
        k = _spread(k_ref[0, k_start:k_start + block_k, :], group, head_dim)
        v = _spread(v_ref[0, k_start:k_start + block_k, :], group, head_dim)
        lanes = k.shape[1]
        dk = jnp.zeros((group * block_k, lanes), jnp.float32)
        dv = jnp.zeros((group * block_k, lanes), jnp.float32)
        if causal:
            # the q-blocks across the diagonal (the first whose last query
            # sees the block's first key, on), then those wholly after it
            first = k_start // block_q
            full = min((k_start + block_k - 1 + block_q - 1) // block_q,
                       num_q_blocks)
        else:
            first = full = 0
        for i in range(first, num_q_blocks):
            start = i * block_q
            rows = slice(start, start + block_q)
            q = q_ref[0, rows, :]
            do = do_ref[0, rows, :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [bq, g*bk]
            if i < full:
                s = _causal_mask(s, start, k_start, group)
            by_head = lambda col: jnp.concatenate(
                [_lanes(col[h, rows, :], block_k) for h in range(group)],
                axis=1)
            # masked entries underflow to exact zero: they shed no grad
            p = jnp.exp(s - by_head(lse_col))
            dv = dv + jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # [g*bk, lanes]
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)         # [bq, g*bk]
            ds = (p * (dp - by_head(delta_col))).astype(q.dtype)
            dk = dk + jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # [g*bk, lanes]
            dq_acc[rows, :] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # [bq, lanes]
        dk_ref[0, k_start:k_start + block_k, :] = (
            _gather_heads(dk, group, head_dim, block_k)
            * scale).astype(dk_ref.dtype)
        dv_ref[0, k_start:k_start + block_k, :] = _gather_heads(
            dv, group, head_dim, block_k).astype(dv_ref.dtype)
    dq_ref[0, :, :] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_impl(q, k, v, o, lse, do, causal, block_q, block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, h, d = q.shape
    group = _group(h, d)
    lanes = group * d
    # delta[b,h,i] = sum_d do*o — the rowwise correction term; cheap
    # elementwise work XLA fuses fine, so it stays outside the kernel
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).swapaxes(1, 2)[:, :, None, :]  # [b, h, 1, t]
    vma = _varying(q, k, v, do, lse, delta)
    whole = pl.BlockSpec((1, t, lanes), lambda ib, ig: (ib, 0, ig))
    row = pl.BlockSpec((1, group, 1, t), lambda ib, ig: (ib, ig, 0, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=d ** -0.5, block_q=block_q,
                          block_k=block_k, causal=causal, group=group,
                          head_dim=d),
        grid=(b, h // group),
        in_specs=[whole, whole, whole, whole, row, row],
        out_specs=[whole, whole, whole],
        out_shape=[jax.ShapeDtypeStruct((b, t, h * d), x.dtype, vma=vma)
                   for x in (q, k, v)],
        scratch_shapes=[pltpu.VMEM((t, lanes), jnp.float32),
                        pltpu.VMEM((group, t, _STATS_LANES), jnp.float32),
                        pltpu.VMEM((group, t, _STATS_LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="flash_attention_bwd",
        interpret=interpret,
    )(_lane_blocks(q), _lane_blocks(k), _lane_blocks(v), _lane_blocks(do),
      lse, delta)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, fwd_blocks, bwd_blocks, interpret):
    o, _ = _fwd_impl(q, k, v, causal, *fwd_blocks, interpret)
    return o


def _flash_fwd(q, k, v, causal, fwd_blocks, bwd_blocks, interpret):
    o, lse = _fwd_impl(q, k, v, causal, *fwd_blocks, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, fwd_blocks, bwd_blocks, interpret, res, do):
    q, k, v, o, lse = res
    return _bwd_impl(q, k, v, o, lse, do, causal, *bwd_blocks, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)

#: jitted, so that a model's layers trace and lower the kernels once
#: between them and not once each, as ``decode_attention.pool_attention``
_flash_jit = jax.jit(_flash, static_argnums=(3, 4, 5, 6))


def flash_attention(q, k, v, causal: bool = True,
                    block_q: int = None, block_k: int = None,
                    interpret: bool = False):
    """Fused attention over ``[batch, seq, heads, head_dim]`` tensors.

    Differentiable (``custom_vjp``; backward recomputes probability
    tiles). Blocks named here (both or neither) serve the forward and the
    backward; else each follows from the shape (:func:`_tiles`). This
    function asserts :func:`fits` rather than silently padding.
    ``interpret=True`` runs on CPU for tests.
    """
    if not fits(q.shape, q.dtype, block_q, block_k):
        raise ValueError(
            f"flash_attention fits() rejected shape {q.shape} at blocks "
            f"({block_q}, {block_k})")
    if block_q is None:
        fwd, bwd = _tiles(q.shape, np.dtype(q.dtype).itemsize)
    else:
        fwd = bwd = (block_q, block_k)
    return _flash_jit(q, k, v, causal, fwd, bwd, interpret)


def _per_device() -> bool:
    """Whether a call traced here runs on one device as it is: inside a
    ``shard_map`` that is manual over every mesh axis that splits
    anything, on a mesh of one device, or, with no mesh in the trace, in
    a process of one device. Elsewhere GSPMD may split the arrays, and a
    Pallas call it cannot split would gather them whole onto every
    device."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return jax.device_count() == 1
    return all(size == 1 or kind == jax.sharding.AxisType.Manual
               for size, kind in zip(mesh.axis_sizes, mesh.axis_types))


def train_dispatch(q) -> bool:
    """Trace-time choice for the no-cache branch of ``models/gpt.py``: on
    a TPU, on one device as traced (:func:`_per_device`; GSPMD training
    over a mesh keeps the XLA path, which it partitions), and the training
    kernel takes the queries' shape and dtype. ``q``: anything with a
    shape and a dtype."""
    return _on_tpu() and _per_device() and fits(q.shape, q.dtype)


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
