"""A short block's attention over the rows of a rectangular KV pool, read
in the pool and only as far as each lane has written (DESIGN.md §14).

The decode step (``[token, ghost]``), the verify step and a short chunk
attend ``t`` query positions a lane over that lane's row of the pool's
``[rows, max_len, width]`` leaves. The XLA form gathers every lane's whole
row (``ops/cache_rows.gather_rows``) and contracts over all ``max_len``
keys; a lane that holds a third of its row reads three times what it
needs. :func:`pool_attention` is one Pallas call that takes the two leaves
as they lie in HBM and, lane by lane, streams blocks of
:func:`block_positions` positions of row ``rows[i]`` up to
``min(index[i] + t, max_len)`` through VMEM (two buffers a leaf, the next
block's copy in flight under this block's products, the next lane's first
block under this lane's last), with an online softmax across blocks:
running maximum, running sum and the weighted values in float32. A block
past a lane's length is neither fetched nor multiplied, and no
``[lanes, max_len, width]`` copy of K or V exists.

K and V stay ``[positions, width]`` matrices, every head side by side on
the lanes, as the pool stores them. The heads' queries are spread over the
width as ``models/gpt.py::_attend_rows`` spreads them (zeros outside a
head's own ``head_dim`` columns), so scores and weighted values are one
matrix product a block each. The numbers are ``_attend_rows``'s: K, V and
products in the pool's dtype, float32 logits, softmax and sums, weights
cast to the queries' dtype for P.V, key ``p`` visible to query ``j`` iff
``p <= index + j``, exact-zero weight on a masked key (``MASK_VALUE``
underflows). The online softmax adds the same terms in another order
(NUMERICS.md "Decode-step equivalence").

No flag selects this kernel: ``models/gpt.py`` takes it where
:func:`dispatch` says the backend is a TPU and the shapes fit, and the
code that was there everywhere else. Tests call it with
``interpret=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.ops.attention import MASK_VALUE
from distkeras_tpu.ops.pallas.flash_attention import (_VMEM_BUDGET_BYTES,
                                                      _on_tpu)

#: most query rows (block positions x padded heads) one lane spreads over
#: the width: one pass of the MXU's rows
MAX_QUERY_ROWS = 128

#: heads are padded to whole bfloat16 sublane tiles, so that each
#: position's group of query rows starts on a tile
_HEAD_ROWS = 16

#: share of the VMEM budget the four streamed buffers (K and V, two slots
#: each) may take, and the most positions a block has. A lane's length is
#: rounded up to whole blocks, and a block costs ~0.1 us beside its bytes:
#: at gpt2-medium's line the whole step took 4.35 / 4.67 / 5.15 ms at
#: 128 / 256 / 512 with lanes ~350 long, 5.41 / 5.67 / 6.61 at ~680, and
#: 6.93 / 6.59 / 6.61 with every lane full (PERF.md, PR 38)
_STREAM_SHARE = 7
_MAX_BLOCK = 128


def block_positions(width: int, itemsize: int, max_len: int) -> int:
    """Positions a block: the largest power of two that divides
    ``max_len``, is at most ``_MAX_BLOCK`` and keeps K's and V's two
    buffers each within a ``_STREAM_SHARE``-th of ``_VMEM_BUDGET_BYTES``;
    0 where not even a sublane tile of positions fits or divides."""
    cap = min(_MAX_BLOCK,
              _VMEM_BUDGET_BYTES // _STREAM_SHARE // (4 * width * itemsize))
    block = 1
    while block * 2 <= cap and max_len % (block * 2) == 0:
        block *= 2
    return block if block >= _HEAD_ROWS else 0


def _padded_heads(num_heads: int) -> int:
    return -(-num_heads // _HEAD_ROWS) * _HEAD_ROWS


def fits(q, pool, num_heads: int) -> bool:
    """What the kernel takes: ``[b, t, width]`` queries of a short block
    over ``[rows, max_len, width]`` leaves of the queries' dtype,
    bfloat16 or float32, whole lane tiles wide, a block length that
    divides the row. ``q`` and ``pool``: anything with a shape and a
    dtype."""
    if len(q.shape) != 3 or len(pool.shape) != 3:
        return False
    _, t, width = q.shape
    dtype = np.dtype(pool.dtype)
    if dtype != q.dtype or dtype not in (np.dtype(jnp.bfloat16),
                                         np.dtype(np.float32)):
        return False
    if width != pool.shape[2] or width % 128 or width % num_heads:
        return False
    if t * _padded_heads(num_heads) > MAX_QUERY_ROWS:
        return False
    return block_positions(width, dtype.itemsize, pool.shape[1]) > 0


def dispatch(q, pool, num_heads: int) -> bool:
    """Trace-time choice for ``models/gpt.py``: on a TPU, and the shapes
    fit."""
    return _on_tpu() and fits(q, pool, num_heads)


def _attend_block(q, k, v, base, length, end, m, l, acc, *, t, hp, scale):
    """One block of the online softmax. ``q [t*hp, w]`` spread queries,
    position-major; ``k``, ``v`` ``[block, w]`` at positions ``base ..``;
    ``m``, ``l`` ``[t*hp, 1]``, ``acc [t*hp, w]`` float32. Rows of ``v``
    at or past ``end`` (nothing the lane wrote) are zeroed: a weight of
    exact zero does not hide a NaN."""
    rows, block = q.shape[0], k.shape[0]
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale           # [rows, block]
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, block), 0)
    q_pos = length + sum((row >= j * hp).astype(jnp.int32)
                         for j in range(1, t))
    key_pos = base + jax.lax.broadcasted_iota(jnp.int32, (rows, block), 1)
    logits = jnp.where(key_pos <= q_pos, logits, MASK_VALUE)
    m_new = jnp.maximum(m, logits.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(logits - m_new)
    l = alpha * l + p.sum(axis=-1, keepdims=True)
    written = base + jax.lax.broadcasted_iota(
        jnp.int32, (block, 1), 0) < end
    v = jnp.where(written, v, jnp.zeros_like(v))
    acc = alpha * acc + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l, acc


def _heads_together(l, acc, *, t, hp, head_dim):
    """``[t, w]`` float32 from the spread sums: position ``j``'s rows are
    ``acc[j*hp:(j+1)*hp]``, and row ``h`` of them holds head ``h``'s
    values in its own ``head_dim`` columns."""
    width = acc.shape[1]
    head = jax.lax.broadcasted_iota(jnp.int32, (hp, width), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (hp, width), 1)
    own = (col >= head * head_dim) & (col < (head + 1) * head_dim)
    out_row = jax.lax.broadcasted_iota(jnp.int32, (t, width), 0)
    out = jnp.zeros((t, width), jnp.float32)
    for j in range(t):
        mine = acc[j * hp:(j + 1) * hp] / l[j * hp:(j + 1) * hp]
        line = jnp.where(own, mine, 0.0).sum(axis=0, keepdims=True)
        out = jnp.where(out_row == j, line, out)
    return out


def _start(rows: int, width: int):
    """(m, l, acc) before a lane's first block: a finite maximum (that
    block always holds a visible key, position 0), so no inf enters the
    sums."""
    return (jnp.full((rows, 1), MASK_VALUE, jnp.float32),
            jnp.zeros((rows, 1), jnp.float32),
            jnp.zeros((rows, width), jnp.float32))


def _kernel(rows_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, slot_ref, *,
            t, hp, head_dim, block, max_len, lanes, scale):
    """Grid ``(lanes,)``, in order. Lane ``i`` walks its blocks in a
    ``fori_loop`` to its own count; while a block is multiplied the next
    one's copy is in flight, and under a lane's last block the next
    lane's first. ``slot_ref[0]`` says which buffer holds this lane's
    first block."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)

    def copies(lane, j, slot):
        at = (rows_ref[lane], pl.ds(pl.multiple_of(j * block, block), block))
        return (pltpu.make_async_copy(k_hbm.at[at], k_buf.at[slot],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[at], v_buf.at[slot],
                                      sems.at[1, slot]))

    @pl.when(i == 0)
    def _prime():
        slot_ref[0] = 0
        for c in copies(0, 0, 0):
            c.start()

    first = slot_ref[0]
    length = len_ref[i]
    end = jnp.minimum(length + t, max_len)
    count = (end + block - 1) // block
    q = q_ref[0]

    def body(j, carry):
        slot = (first + j) % 2

        @pl.when(j + 1 < count)
        def _next_block():
            for c in copies(i, j + 1, 1 - slot):
                c.start()

        @pl.when((j + 1 == count) & (i + 1 < lanes))
        def _next_lane():
            for c in copies(i + 1, 0, 1 - slot):
                c.start()

        for c in copies(i, j, slot):
            c.wait()
        return _attend_block(q, k_buf[slot], v_buf[slot], j * block, length,
                             end, *carry, t=t, hp=hp, scale=scale)

    _, l, acc = jax.lax.fori_loop(0, count, body, _start(*q.shape))
    slot_ref[0] = (first + count) % 2
    o_ref[0] = _heads_together(l, acc, t=t, hp=hp, head_dim=head_dim)


def pool_attention(q, k_pool, v_pool, rows, index, num_heads: int,
                   interpret: bool = False):
    """Attention of ``q [b, t, width]``, lane ``i``'s ``t`` positions at
    ``index[i] ..``, over row ``rows[i]`` of ``k_pool`` / ``v_pool``
    ``[rows, max_len, width]`` (``rows=None``: lane i reads row i), which
    already hold the block's own lines. Returns ``[b, t, width]`` in
    ``q``'s dtype. Shapes must satisfy :func:`fits`."""
    if not fits(q, k_pool, num_heads):
        raise ValueError(
            f"pool_attention fits() rejected queries {q.shape} over a pool "
            f"of {k_pool.shape} {k_pool.dtype}, {num_heads} heads")
    if rows is None:
        rows = jnp.arange(q.shape[0])
    return _pool_attention(q, k_pool, v_pool, rows, index, num_heads,
                           interpret)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _pool_attention(q, k_pool, v_pool, rows, index, num_heads, interpret):
    """:func:`pool_attention` proper. Jitted, so that a step's layers
    trace and lower the kernel once between them and not once each (24
    traces a ladder rung cost the engine 10 s of set-up on the chip's
    host; PERF.md, PR 38)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, width = q.shape
    max_len = k_pool.shape[1]
    head_dim = width // num_heads
    hp = _padded_heads(num_heads)
    block = block_positions(width, k_pool.dtype.itemsize, max_len)
    # position-major spread: row j*hp + h is head h's query at position j,
    # zeros outside its own columns and in the padded heads' rows
    own = (jnp.arange(width)[None, :] // head_dim
           == jnp.arange(hp)[:, None])[None, None]           # [1,1,hp,w]
    spread = jnp.where(own, q[:, :, None, :], 0).reshape(b, t * hp, width)
    lane_block = lambda i, *_: (i, 0, 0)
    leaf_spec = pl.BlockSpec(memory_space=pl.ANY)   # as it lies in HBM
    out = pl.pallas_call(
        functools.partial(
            _kernel, t=t, hp=hp, head_dim=head_dim, block=block,
            max_len=max_len, lanes=b, scale=head_dim ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[pl.BlockSpec((1, t * hp, width), lane_block),
                      leaf_spec, leaf_spec],
            out_specs=pl.BlockSpec((1, t, width), lane_block),
            scratch_shapes=[pltpu.VMEM((2, block, width), k_pool.dtype),
                            pltpu.VMEM((2, block, width), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((b, t, width), jnp.float32),
        # in order: a lane starts the next lane's first copy
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="pool_attention",
        interpret=interpret,
    )(rows.astype(jnp.int32), index.astype(jnp.int32), spread, k_pool,
      v_pool)
    return out.astype(q.dtype)
