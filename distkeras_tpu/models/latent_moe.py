"""Latent-attention decoder with routed experts, held as one chip's share.

A pre-norm decoder family (RMSNorm, SwiGLU, untied bias-free head) whose
two sub-layers are the ones today's large sparse models use:

- **latent attention** (the DeepSeek-V2/V3 form): keys and values are
  up-projections of one ``kv_lora_rank``-wide latent a position, and one
  rotary key shared by all heads, so the cache line of a position is
  ``[latent | RoPE(k_rope)]`` (``kv_lora_rank + qk_rope_head_dim`` values)
  instead of ``heads x (key + value)``. Rotary embedding is on
  interleaved pairs under YaRN scaling; the query of position ``p`` is
  also multiplied by ``1 + beta * ln(1 + floor(p / original_max_len))``.
- **routed experts, dropless, top-k**: a float32 softmax router over all
  ``num_experts``, the ``experts_per_token`` largest renormalised, no
  capacity and no dropped token, plus a shared expert every token takes.
  The layer is told which experts it holds (``expert_share = (index,
  of)``: experts ``index * num_experts / of`` onward) and computes the
  shared expert plus ITS experts' part of the result. What the absent
  experts would add is left out, and nothing here stands in for the
  chips that hold them: on one chip the layer runs without its exchange.

Parameters are ``dtype`` (bfloat16 as served); router and norm vectors
are float32, and the router's product, every norm, the attention softmax
and the logits are computed in float32. The residual stream is float32.

Cache contract (DESIGN.md section 14, the one models/gpt.py keeps): the
model owns its cache's leaves (:meth:`LatentMoELM.init_cache`,
:meth:`LatentMoELM.cache_bytes_per_row`): one ``{"kv": [rows, max_len,
line]}`` leaf a layer. A cache call writes the block's lines in place
FIRST, then attends the lanes' rows where they lie (``cache_rows``, a
gather in runs the compiler takes in place). It returns ``(logits,
new_cache, routed)``; ``routed`` is ``[layers, batch, t, experts_held]``
booleans, token by token which held experts it was sent to (the serving
step reduces it to tokens per held expert; nothing else leaves the
device).

One layer, two forms, chosen from the block's shape (no option):

- a LONG block (prefill) expands keys and values per head from the
  latents it attends and takes its queries in blocks of
  :data:`_QUERY_BLOCK`, so no ``[heads, t, t]`` score tensor is built;
- a SHORT block (``t <= _ABSORB_MAX_BLOCK``: decode, verify) absorbs the
  up-projections into the query and the output, and reads the cached
  lines as they lie: ``line`` values a position, not ``heads x 256``.

Both attend every position of the rows they are given, masked by
position, so both are right at any ``cache_index``; a prefill is cheap
because the serving step hands it a fresh row as long as its bucket
(:meth:`LatentMoELM.prefill_row_len`).
"""

from __future__ import annotations

import math
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.ops.attention import MASK_VALUE
from distkeras_tpu.ops.cache_rows import gather_rows

#: longest block that takes the absorbed form. By operations the two
#: forms meet near t = 150 (absorbed pays 2.25 x the score and value
#: products, expanded pays the up-projection of every key it attends);
#: by bytes the absorbed form reads a line where the expanded one writes
#: and reads heads x 256 values a key. On the v5e at the published widths
#: (attention core alone, ms; my chip run, PR 27): 32 lanes x 2 positions
#: over 4608-position rows 0.88 absorbed, 9.73 expanded; x 8 positions
#: 1.68, 10.09; one row as long as its block: level (0.58 to 0.68) from 8
#: to 256 positions, 0.83 against 0.68 at 512, 4.09 against 1.67 at 2048.
_ABSORB_MAX_BLOCK = 64

#: queries a long block attends at a time
_QUERY_BLOCK = 256

#: most tokens the expert layer puts through every held expert (each
#: token keeping its own gates' part) instead of sorting them by expert.
#: On the v5e at the published widths, 32 held experts, one layer: 256
#: tokens 3.49 ms against 5.99 sorted, 128 tokens 3.07 against 5.95; 1024
#: tokens 11.84 against 6.56 (my chip run, PR 27, the sorted rows then
#: through ``jax.lax.ragged_dot``, ~6 ms however few they were): the
#: masked product reads the same 1.6 GB and multiplies 32 x the rows
_DENSE_MAX_TOKENS = 256

#: rows of a block of the many-token expert path: a block is one expert's,
#: so an expert with r tokens costs ceil(r / 256) passes over its matrices.
#: At the published widths a pass reads 10 to 17 MB a matrix (12 to 20 us)
#: and 256 rows multiply in about as long: smaller blocks wait for the
#: weights, larger ones multiply padding. Why a loop and not
#: ``jax.lax.ragged_dot`` (my chip runs, PR 32): that kernel took 17.5 ms a
#: layer for 12 288 sorted rows over 64 groups of 2688 x 1856, 3.5 % of the
#: MXU's peak, plus a 2 ms relayout of each weight stack a call, where the
#: loop takes 3.75; at 32 groups of 4096 x 2048 a prefill of six layers took
#: 75.7 ms with the kernel and 67.4 with the loop (PERF.md section 6)
_EXPERT_BLOCK = 256

#: lanes a short block attends at a time: a group's gathered rows (57 MB
#: at 16 lanes of 4608 positions) are what has to stay in fast memory
#: between the gather and the two products. The whole 128-lane decode step
#: on the v5e, ms (my chip runs, PR 27): one group of 128 53.1, 64 52.0,
#: 32 44.7, 16 40.5 (experts through the grouped product); 32 30.5,
#: 16 26.6, 8 26.5 (experts as they are now)
_LANE_GROUP = 16


def rms_norm(x, scale, eps):
    """``scale * x / sqrt(mean(x^2) + eps)`` in float32."""
    x = x.astype(jnp.float32)
    return scale * x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def yarn_inv_freq(dim: int, theta: float, factor: float, beta_fast: float,
                  beta_slow: float, original_max_len: int) -> np.ndarray:
    """YaRN's ``dim / 2`` rotary frequencies: ``theta^(-2j/dim)`` where a
    pair turns more than ``beta_fast`` times within the original length,
    that over ``factor`` where it turns less than ``beta_slow`` times,
    and a linear ramp over the pair index between the two corners
    (floored and ceiled, as DeepSeek-V3's reference code does)."""
    def corner(rotations):
        return dim * math.log(original_max_len / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(corner(beta_fast)), 0)
    high = min(math.ceil(corner(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    plain = theta ** (-np.arange(0, dim, 2) / dim)
    return (plain / factor * ramp + plain * (1 - ramp)).astype(np.float32)


def softmax_scale(qk_head_dim: int, factor: float,
                  mscale_all_dim: float = 1.0) -> float:
    """``qk_head_dim^-1/2 * (0.1 * mscale_all_dim * ln(factor) + 1)^2``:
    DeepSeek-V3's rule for YaRN with ``mscale == mscale_all_dim`` (the
    rotary tables then carry no factor of their own)."""
    mscale = 0.1 * mscale_all_dim * math.log(factor) + 1.0 \
        if factor > 1 else 1.0
    return qk_head_dim ** -0.5 * mscale * mscale


def position_scale(pos, beta: float, original_max_len: int):
    """``1 + beta * ln(1 + floor(pos / original_max_len))``, float32: the
    factor on the query of position ``pos`` (1 below the original
    length)."""
    return 1.0 + beta * jnp.log1p(
        jnp.floor(pos.astype(jnp.float32) / original_max_len))


def rope_interleaved(x, pos, inv_freq):
    """Rotate the pairs ``(x[2j], x[2j+1])`` of the last dimension by
    ``pos * inv_freq[j]``. ``x`` is ``[b, t, ..., dim]``, ``pos`` ``[b,
    t]``; float32 out. (A roll and a select, so the pairs never leave
    the lanes they lie in.)"""
    x = x.astype(jnp.float32)
    angle = pos.astype(jnp.float32)[..., None] * inv_freq     # [b, t, dim/2]
    shape = angle.shape[:2] + (1,) * (x.ndim - 3) + (x.shape[-1],)
    cos = jnp.repeat(jnp.cos(angle), 2, axis=-1).reshape(shape)
    sin = jnp.repeat(jnp.sin(angle), 2, axis=-1).reshape(shape)
    even = jnp.arange(x.shape[-1]) % 2 == 0
    turned = jnp.where(even, -jnp.roll(x, -1, axis=-1),
                       jnp.roll(x, 1, axis=-1))
    return x * cos + turned * sin


def _fan_in(key, shape, dtype):
    """Normal, standard deviation ``fan_in^-1/2`` (the second-to-last
    dimension: a stack of experts is a stack of matrices)."""
    return (jax.random.normal(key, shape, jnp.float32)
            * shape[-2] ** -0.5).astype(dtype)


_MATRICES = frozenset((
    "q_a", "q_b", "kv_a", "kv_b", "o", "router", "gate", "up", "down",
    "shared_gate", "shared_up", "shared_down", "head"))


def param_init(name: str):
    """The initialiser ``(key, shape, dtype)`` of the parameter called
    ``name`` (the last key of its path): the one place that says how this
    family's weights are drawn, for the modules below and for whoever
    makes the weights a leaf at a time (perf/builders/latent_moe.py).
    Norm vectors one, the embedding unit normal, every matrix and stack
    of matrices :func:`_fan_in`, a router's selection bias normal of
    deviation 0.1 (enough to move a choice, as a trained one does); a name
    it does not know raises."""
    if name.endswith("norm") or "_norm_" in name:
        return nn.initializers.ones
    if name == "tok_embed":
        return nn.initializers.normal(1.0)
    if name == "router_bias":
        return nn.initializers.normal(0.1)
    if name in _MATRICES:
        return _fan_in
    raise KeyError(f"no initialiser for a parameter called {name!r}")


def in_query_blocks(attend, args, block: int):
    """``attend`` over ``args`` (arrays ``[b, t, ...]``, the queries and
    what goes with each) ``block`` positions at a time: a long block's
    scores are then ``[block, R]`` a head at once, not ``[t, R]``. A ragged
    ``t`` is padded to whole blocks and cut after; a ``t`` within one
    block is one call."""
    b, t = args[0].shape[:2]
    block = min(t, block)
    if block == t:
        return attend(args)
    pad = -t % block
    split = lambda a: jnp.moveaxis(
        jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)).reshape(
            (b, (t + pad) // block, block) + a.shape[2:]), 1, 0)
    out = jax.lax.map(attend, tuple(split(a) for a in args))
    return jnp.moveaxis(out, 0, 1).reshape(
        (b, t + pad) + out.shape[3:])[:, :t]


def _attend_expanded(q, rows, pos, w_kvb, dims, scale):
    """Long-block form. ``q [b, t, h, nope + rope]`` (rotated), ``rows
    [b, R, line]`` cached lines, ``pos [b, t]``, ``scale [b, t]`` float32
    (softmax scale times the position's factor). Keys and values of all
    ``R`` positions are up-projected per head once; the queries go
    through in blocks. Returns ``[b, t, h, v]``."""
    rank, nope, rope, v_dim, heads = dims
    b = q.shape[0]
    r = rows.shape[1]
    kv = jnp.einsum("brc,chd->brhd", rows[..., :rank],
                    w_kvb.reshape(rank, heads, nope + v_dim))
    k_rope = jnp.broadcast_to(rows[:, :, None, rank:rank + rope],
                              (b, r, heads, rope))
    k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)
    v = kv[..., nope:]

    def attend(args):
        q_blk, pos_blk, scale_blk = args              # [b, block, ...]
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k).astype(jnp.float32)
        s = s * scale_blk[:, None, :, None]
        mask = jnp.arange(r)[None, None, None, :] <= pos_blk[:, None, :, None]
        p = jax.nn.softmax(jnp.where(mask, s, MASK_VALUE), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)

    return in_query_blocks(attend, (q, pos, scale), _QUERY_BLOCK)


def _attend_absorbed(q, rows, pos, w_kvb, dims, scale):
    """Short-block form, same numbers: the key up-projection goes into
    the query (``q_nope W^K``, ``rank`` wide), the scores are taken
    against the cached lines as they lie, the weighted sum of latents is
    up-projected to values after. Arguments and result as
    :func:`_attend_expanded`."""
    rank, nope, rope, v_dim, heads = dims
    b, t = q.shape[:2]
    r, line = rows.shape[1:]
    w = w_kvb.reshape(rank, heads, nope + v_dim)
    q_lat = jnp.einsum("bthd,chd->bthc", q[..., :nope], w[..., :nope])
    parts = [q_lat.astype(rows.dtype), q[..., nope:]]
    if line > rank + rope:      # a padded line: zeros meet its padding
        parts.append(jnp.zeros(q.shape[:3] + (line - rank - rope,), q.dtype))
    q_line = jnp.concatenate(parts, axis=-1).reshape(b, t * heads, line)
    s = jnp.einsum("bql,bkl->bqk", q_line, rows).astype(jnp.float32)
    s = s.reshape(b, t, heads, r) * scale[:, :, None, None]
    mask = jnp.arange(r)[None, None, None, :] <= pos[:, :, None, None]
    p = jax.nn.softmax(jnp.where(mask, s, MASK_VALUE), axis=-1)
    o_lat = jnp.einsum("bqk,bkl->bql",
                       p.astype(rows.dtype).reshape(b, t * heads, r), rows)
    o_lat = o_lat.reshape(b, t, heads, line)[..., :rank]
    return jnp.einsum("bthc,chd->bthd", o_lat, w[..., nope:])


class LatentAttention(nn.Module):
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rms_eps: float
    inv_freq: Tuple[float, ...]
    softmax_scale: float
    position_beta: float
    original_max_len: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, pos, cache=None, cache_rows=None):
        """``x [b, t, width]`` (normed), ``pos [b, t]``. With ``cache``
        (``{"kv": [rows, R, line]}``) the block's lines are written in
        place first and ``(out, new_cache)`` returns; without, the block
        attends itself."""
        dtype = self.dtype
        width = x.shape[-1]
        heads, rank = self.num_heads, self.kv_lora_rank
        nope, rope = self.qk_nope_head_dim, self.qk_rope_head_dim
        dims = (rank, nope, rope, self.v_head_dim, heads)
        mat = lambda name, *shape: self.param(name, param_init(name), shape,
                                              dtype)
        vec = lambda name, n: self.param(name, param_init(name), (n,),
                                         jnp.float32)
        b, t = x.shape[:2]
        inv_freq = jnp.asarray(self.inv_freq, jnp.float32)
        x = x.astype(dtype)
        c_q = rms_norm(x @ mat("q_a", width, self.q_lora_rank),
                       vec("q_norm", self.q_lora_rank), self.rms_eps)
        q = (c_q.astype(dtype) @ mat("q_b", self.q_lora_rank,
                                     heads * (nope + rope))
             ).reshape(b, t, heads, nope + rope)
        q = jnp.concatenate(
            [q[..., :nope],
             rope_interleaved(q[..., nope:], pos, inv_freq).astype(dtype)],
            axis=-1)
        kv_a = x @ mat("kv_a", width, rank + rope)
        lines = jnp.concatenate(
            [rms_norm(kv_a[..., :rank], vec("kv_norm", rank),
                      self.rms_eps).astype(dtype),
             rope_interleaved(kv_a[..., rank:], pos, inv_freq).astype(dtype)],
            axis=-1)
        w_kvb = mat("kv_b", rank, heads * (nope + self.v_head_dim))
        w_o = mat("o", heads * self.v_head_dim, width)
        scale = self.softmax_scale * position_scale(
            pos, self.position_beta, self.original_max_len)
        attend = _attend_absorbed if t <= _ABSORB_MAX_BLOCK \
            else _attend_expanded
        new_cache = None
        if cache is None:
            out = attend(q, lines, pos, w_kvb, dims, scale)
        else:
            leaf = cache["kv"]
            line = leaf.shape[-1]
            if line > lines.shape[-1]:
                lines = jnp.pad(lines, ((0, 0), (0, 0),
                                        (0, line - lines.shape[-1])))
            rows = jnp.arange(b) if cache_rows is None else cache_rows
            # in place, first; mode="drop": the decode step's ghost
            # position past the row's end must not clamp onto its last cell
            leaf = leaf.at[rows[:, None], pos].set(lines.astype(leaf.dtype),
                                                   mode="drop")
            new_cache = {"kv": leaf}
            lanes = b
            if attend is _attend_absorbed and cache_rows is not None \
                    and b % _LANE_GROUP == 0:
                lanes = _LANE_GROUP
            out = jnp.concatenate([
                attend(q[g:g + lanes],
                       gather_rows(leaf, None if cache_rows is None
                                   else cache_rows[g:g + lanes]),
                       pos[g:g + lanes], w_kvb, dims, scale[g:g + lanes])
                for g in range(0, b, lanes)], axis=0)
        out = out.reshape(b, t, heads * self.v_head_dim).astype(dtype) @ w_o
        return out, new_cache


def _swiglu(x, gate, up, down):
    h = jax.nn.silu((x @ gate).astype(jnp.float32)) \
        * (x @ up).astype(jnp.float32)
    return h.astype(x.dtype) @ down


def _relu2(h):
    """``relu(h)^2``, the ungated expert's activation, written ``relu(h) *
    h``: the same number for every finite ``h``, and XLA:CPU does not then
    fold the ``relu`` into the bfloat16 product before it, a form it cannot
    run (``DotThunk``: BF16 x BF16 = F32 unimplemented)."""
    return jax.nn.relu(h) * h


@jax.jit
def _in_expert_blocks(xb, gate, up, down, group):
    """The many-token expert product. ``xb [n, d]`` tokens, ``gate`` (or
    ``None``: the ungated form), ``up [held, d, width]``, ``down [held,
    width, d]``, ``group [n * k]`` each assignment's held expert (``held``
    for an absent one, which sorts behind every group so that no product
    touches it). The assignments, sorted by expert, go into blocks of
    :data:`_EXPERT_BLOCK` rows, each block one expert's (its last padded
    with rows of zeros), and a loop runs over the blocks in use: two or
    three products a block against that expert's matrices where they lie.
    Back in their own order, ``[n * k, d]``; an absent expert's assignment
    reads a row that is not its own. Jitted so that a model's expert layers
    share one trace and one lowered function a shape."""
    blk, dtype = _EXPERT_BLOCK, xb.dtype
    held, d = up.shape[0], xb.shape[-1]
    k = group.size // xb.shape[0]
    sizes = jnp.sum(group[:, None] == jnp.arange(held)[None, :],
                    axis=0, dtype=jnp.int32)
    order = jnp.argsort(group, stable=True)
    blocks_of = -(-sizes // blk)
    ends = jnp.cumsum(blocks_of)
    sorted_group = group[order]
    of = jnp.minimum(sorted_group, held - 1)
    rank = jnp.arange(order.size) - (jnp.cumsum(sizes) - sizes)[of]
    n_blocks = order.size // blk + held   # sum of ceil(size / blk)
    dest = jnp.where(sorted_group < held,
                     (ends - blocks_of)[of] * blk + rank,
                     n_blocks * blk)
    rows = jnp.zeros((n_blocks * blk, d), dtype).at[dest].set(
        xb[order // k], mode="drop")
    expert_of = jnp.minimum(jnp.searchsorted(
        ends, jnp.arange(n_blocks), side="right"), held - 1)

    def one_block(b, y):
        mine = lambda w: jax.lax.dynamic_index_in_dim(
            w, expert_of[b], keepdims=False)
        x_b = jax.lax.dynamic_slice_in_dim(rows, b * blk, blk)
        wide = lambda w: jnp.dot(
            x_b, mine(w), preferred_element_type=jnp.float32)
        h = jax.nn.silu(wide(gate)) * wide(up) if gate is not None \
            else _relu2(wide(up))
        out = jnp.dot(h.astype(dtype), mine(down),
                      preferred_element_type=jnp.float32)
        return jax.lax.dynamic_update_slice_in_dim(
            y, out.astype(dtype), b * blk, axis=0)

    y = jax.lax.fori_loop(0, ends[-1], one_block, jnp.zeros_like(rows))
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.size, dtype=order.dtype))
    return y[jnp.minimum(dest[back], n_blocks * blk - 1)]


class ExpertShare(nn.Module):
    """The expert layer as one of ``of`` chips computes it: the shared
    expert, and for each token the part of its top-k mixture that the
    experts held here give.

    Two scoring rules: ``"softmax"`` over all the router's outputs, the k
    largest renormalised; ``"sigmoid"``, the k largest of ``sigmoid(logit)
    + b`` (``b`` a selection bias a router output, which chooses and does
    not weigh), weighted by their sigmoids renormalised. Two expert forms:
    ``"swiglu"``, ``down(silu(gate x) * up x)``; ``"relu2"``,
    ``down(relu(up x)^2)`` with no gate matrix. ``shared_width`` is the
    shared expert's width where it is not a routed expert's. The default
    of each field is what :class:`LatentMoELM` had before there was a
    choice."""
    width: int
    num_experts: int
    experts_per_token: int
    expert_share: Tuple[int, int]
    routed_scaling: float = 1.0
    dtype: jnp.dtype = jnp.bfloat16
    scoring: str = "softmax"
    activation: str = "swiglu"
    shared_width: int = 0

    @nn.compact
    def __call__(self, x):
        """``x [n, model width]`` (normed, float32) -> ``(out [n, model
        width] float32, routed [n, held] bool)``."""
        dtype, k = self.dtype, self.experts_per_token
        index, of = self.expert_share
        held = self.num_experts // of
        n, d = x.shape
        mat = lambda name, *shape: self.param(name, param_init(name), shape,
                                              dtype)
        with jax.named_scope("moe.route"):
            w_r = self.param("router", param_init("router"),
                             (d, self.num_experts), jnp.float32)
            logits = jnp.dot(x.astype(jnp.float32), w_r,
                             precision=jax.lax.Precision.HIGHEST)
            if self.scoring == "softmax":
                top, chosen = jax.lax.top_k(
                    jax.nn.softmax(logits, axis=-1), k)            # [n, k]
            else:
                scores = jax.nn.sigmoid(logits)
                _, chosen = jax.lax.top_k(scores + self.param(
                    "router_bias", param_init("router_bias"),
                    (self.num_experts,), jnp.float32), k)
                top = jnp.take_along_axis(scores, chosen, axis=-1)
            top = top / jnp.sum(top, axis=-1, keepdims=True) \
                * self.routed_scaling
            local = chosen - index * held
            here = (local >= 0) & (local < held)
            sent = local[..., None] == jnp.arange(held)      # [n, k, held]
            routed = jnp.any(sent, axis=1)
        xb = x.astype(dtype)
        gated = self.activation == "swiglu"
        gate = mat("gate", held, d, self.width) if gated else None
        up = mat("up", held, d, self.width)
        down = mat("down", held, self.width, d)
        with jax.named_scope("moe.experts"):
            if n <= _DENSE_MAX_TOKENS:
                # few tokens: every held expert on every token, and each
                # token keeps its own gates' part (zero for the rest)
                f32 = dict(preferred_element_type=jnp.float32)
                every = lambda w: jnp.einsum("nd,edf->enf", xb, w, **f32)
                h = jax.nn.silu(every(gate)) * every(up) if gated \
                    else _relu2(every(up))
                y = jnp.einsum("enf,efd->end", h.astype(dtype), down, **f32)
                mine = jnp.sum(jnp.where(sent, top[..., None], 0.0),
                               axis=1)                             # [n, held]
                y = jnp.sum(y * mine.T[:, :, None], axis=0)
            else:
                # many tokens: each assignment through its own expert
                group = jnp.where(here, local, held).reshape(-1)   # [n * k]
                y = _in_expert_blocks(xb, gate, up, down, group)
                y = y.reshape(n, k, d).astype(jnp.float32)
                # a select, not a product: an absent expert's assignment
                # reads a row that is not its own
                y = jnp.sum(jnp.where(here[..., None], y * top[..., None],
                                      0.0), axis=1)
        width = self.shared_width or self.width
        with jax.named_scope("moe.shared"):
            if gated:
                shared = _swiglu(xb, mat("shared_gate", d, width),
                                 mat("shared_up", d, width),
                                 mat("shared_down", width, d))
            else:
                shared = _relu2((xb @ mat("shared_up", d, width)).astype(
                    jnp.float32)).astype(dtype) @ mat("shared_down", width, d)
            y = y + shared.astype(jnp.float32)
        return y, routed


class LatentMoELM(nn.Module):
    """The decoder. Sizes are those a chip holds: ``vocab_size`` rows of
    the vocabulary (ids, logits and argmax are over that slice) and
    ``num_experts / expert_share[1]`` experts a layer; ``num_experts`` and
    ``experts_per_token`` are the router's published width and top-k."""
    vocab_size: int
    max_len: int
    num_layers: int
    width: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    moe_width: int
    num_experts: int
    experts_per_token: int
    expert_share: Tuple[int, int] = (0, 1)
    routed_scaling: float = 1.0
    rms_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 1.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_original_max_len: int = 8192
    rope_mscale_all_dim: float = 1.0
    position_beta: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16

    @property
    def experts_held(self) -> int:
        return self.num_experts // self.expert_share[1]

    @property
    def cache_line(self) -> int:
        """Values a cached position holds a layer: the latent and the
        one rotary key, padded up to whole 128-lane tiles (the form the
        TPU stores as written; tests/test_decode_layout.py)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    def init_cache(self, batch: int, dtype=None, positions=None):
        """Zeroed cache for ``batch`` rows: a tuple, one ``{"kv":
        [batch, max_len, cache_line]}`` a layer (``positions`` in
        ``max_len``'s place, where a prefill asks for its fresh row), in
        ``dtype`` (the model's own by default)."""
        shape = (batch, positions or self.max_len, self.cache_line)
        return tuple({"kv": jnp.zeros(shape, dtype or self.dtype)}
                     for _ in range(self.num_layers))

    def cache_bytes_per_row(self, dtype=None) -> int:
        return self.num_layers * self.max_len * self.cache_line \
            * np.dtype(dtype or self.dtype).itemsize

    def prefill_row_len(self, block: int) -> int:
        """Positions of the fresh row a ``block``-token prefill writes
        and attends: the block's own, no more."""
        return block

    @nn.compact
    def __call__(self, input_ids, train: bool = False, cache=None,
                 cache_index=None, page_table=None, cache_rows=None):
        del train                       # no dropout: serving only
        if page_table is not None:
            raise ValueError(
                "LatentMoELM keeps one latent line a position and has no "
                "paged form; serve it from the rectangular KVCachePool")
        ids = input_ids.astype(jnp.int32)
        b, t = ids.shape
        if cache is None:
            pos = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
        else:
            pos = cache_index[:, None] + jnp.arange(t)[None, :]
        embed = self.param("tok_embed", param_init("tok_embed"),
                           (self.vocab_size, self.width), self.dtype)
        x = embed[ids].astype(jnp.float32)
        inv_freq = tuple(float(f) for f in yarn_inv_freq(
            self.qk_rope_head_dim, self.rope_theta, self.rope_factor,
            self.rope_beta_fast, self.rope_beta_slow,
            self.rope_original_max_len))
        scale = softmax_scale(self.qk_nope_head_dim + self.qk_rope_head_dim,
                              self.rope_factor, self.rope_mscale_all_dim)
        norm = lambda name, a: rms_norm(
            a, self.param(name, param_init(name), (self.width,),
                          jnp.float32), self.rms_eps)
        new_cache, routed = [], []
        for i in range(self.num_layers):
            with jax.named_scope("attn.latent"):
                y, layer_cache = LatentAttention(
                    self.num_heads, self.q_lora_rank, self.kv_lora_rank,
                    self.qk_nope_head_dim, self.qk_rope_head_dim,
                    self.v_head_dim, self.rms_eps, inv_freq, scale,
                    self.position_beta, self.rope_original_max_len,
                    self.dtype, name=f"attn_{i}")(
                        norm(f"attn_norm_{i}", x), pos,
                        None if cache is None else cache[i], cache_rows)
            x = x + y.astype(jnp.float32)
            y, sent = ExpertShare(
                self.moe_width, self.num_experts, self.experts_per_token,
                self.expert_share, self.routed_scaling, self.dtype,
                name=f"moe_{i}")(
                    norm(f"moe_norm_{i}", x).reshape(b * t, self.width))
            x = x + y.reshape(b, t, self.width)
            new_cache.append(layer_cache)
            routed.append(sent.reshape(b, t, -1))
        with jax.named_scope("head"):
            head = self.param("head", param_init("head"),
                              (self.width, self.vocab_size), self.dtype)
            logits = jnp.dot(norm("final_norm", x).astype(self.dtype), head,
                             preferred_element_type=jnp.float32)
        if cache is None:
            return logits
        return logits, tuple(new_cache), jnp.stack(routed)


def latent_moe_tiny(**kw) -> LatentMoELM:
    """Test-sized: every mechanism present, float32."""
    defaults = dict(
        vocab_size=128, max_len=64, num_layers=2, width=32, num_heads=4,
        q_lora_rank=16, kv_lora_rank=24, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=12, moe_width=16, num_experts=8,
        experts_per_token=2, expert_share=(0, 2), rope_factor=4.0,
        rope_original_max_len=16, position_beta=0.1, dtype=jnp.float32)
    defaults.update(kw)
    return LatentMoELM(**defaults)
