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

**Learned sparse attention** (``index_heads > 0``; DeepSeek-V3.2's
indexer). Beside the latent line a position caches one **index key**, and a
query attends only the ``index_topk`` positions whose index score is
largest. For a layer with normed input ``x_t``, query latent ``c_t =
RMSNorm(x_t W_qa)`` (the one above) and cached positions ``s <= t``:

- index query, ``index_heads`` of ``index_dim``: ``q_tj = (c_t W_iq)_j``;
  index key, one of ``index_dim``: ``k_s = LayerNorm(x_s W_ik)`` (scale and
  bias, eps 1e-6). Rotary position on the FIRST ``qk_rope_head_dim`` values
  of each, interleaved pairs, the attention's frequencies; ``k_s`` is cached
  after the rotation (leaf ``"ik"``, written in place with the line);
- head weights ``w_tj = (x_t W_iw)_j * index_heads^-1/2 * index_dim^-1/2``;
- index score ``I_ts = sum_j w_tj relu(q_tj . k_s)``: bfloat16 products,
  float32 sums;
- selection ``S_t``: the ``min(t + 1, index_topk)`` positions ``s <= t`` of
  largest ``I_ts``, ties to the lower position. Exact: the same set as a
  full stable sort. Every position past ``t`` (a ghost, bucket padding, a
  freed slot's stale lines, the scratch row) scores ``-inf`` BEFORE the
  selection: a top-k chooses before any mask, so a mask after it would
  come too late;
- attention: the softmax above over ``s in S_t`` only.

The one layer then has a third form, again chosen from the block's shape:
a LONG block turns the selection into a mask over its query block's scores
(:func:`select_mask`: only the k-th largest score of a row is needed, and
that is searched for, not sorted for) and attends in the expanded form
under it (a whole prompt in a few stretches of queries, each over the rows
up to its own end and not over all of them: ``_CAUSAL_STRETCHES``); a
SHORT block turns the same mask into positions
(:func:`select_top`), gathers those lines from the leaf where they lie and
attends them in the absorbed form.

**Layers of two kinds** (``layer_kinds``; dots3-note-prev). ``"F"`` is the
layer above, full attention (under the indexer, if there is one); ``"S"``
is the same latent attention at sizes of its own (:class:`WindowSizes`)
under a causal **window**: ``S_t = {s : t - window < s <= t}``. A window
layer's cache is a **ring** (leaf ``"ring": [rows, cells, line]``, position
``p`` in cell ``p mod cells``): it holds ``cells`` positions however long
the context, so it has no ``max_len`` axis, is declared a state leaf
(``cache_state_leaves``), is overwritten whole at admission and is hidden
by no length mask: :func:`ring_holds`, arithmetic on the lane's length,
says which position each cell holds. A LONG block (a whole prompt) attends
itself in a band of keys a query block (:func:`_attend_band`) and then
builds the ring from its REAL positions; a SHORT block writes into the ring
first and attends it absorbed, as it lies. A cache call of a model under
a selection (one that declares ``cache_select_leaves``) also returns
``attended``, ``[layers, batch, t]`` int32: the positions each query
attended, a window layer's windows among them.

**A residual of several streams** (``hc_mult = n > 1``; manifold-constrained
hyper-connections, mHC, arXiv:2512.24880, on hyper-connections,
arXiv:2409.19606). The residual is ``n`` streams of the model's width, held
as one float32 array ``x [b, t, n * width]`` (stream ``i`` the ``i``-th
``width`` values: no axis of ``n`` lies in a tile). The embedding is copied
into every stream; around each sub-layer ``F`` (a layer's attention, its
dense MLP or its expert layer), with parameters of its own (``phi_pre,
phi_post [n width, n]``, ``phi_res [n width, n^2]``, ``b_pre, b_post [n]``,
``b_res [n, n]``, scalars ``alpha_*``):

- ``v = RMSNorm(x)`` over all ``n * width`` values, no scale of its own;
  ``H_pre = sigmoid(alpha_pre v phi_pre + b_pre)``, ``H_post = 2
  sigmoid(alpha_post v phi_post + b_post)``, ``H_res =
  SinkhornKnopp(exp(clip(alpha_res mat(v phi_res) + b_res)))``: the
  iterations ``M <- T_r(T_c(M))`` (columns, then rows, each divided by its
  sum plus ``hc_eps``), row-major ``mat`` (:class:`HyperConnection`);
- ``h = sum_i H_pre[i] x_i``; ``y = F(h)``, with ``F``'s own pre-norm;
- ``x_i <- sum_j H_res[i, j] x_j + H_post[i] y``.

The head reads ``sum_i x_i`` (HC's final sum) through the final norm. The
mapping is float32 throughout (its product at ``precision=HIGHEST``; the
mixes are products and sums of single values, not contractions). The cache
is untouched: the streams are a token's activations, and the cached line is
what attention's own projections make of ``h``.

Two switches every layer of a model shares, both off by default:
``head_gate``, a sigmoid gate a head on the attention output, ``g_t =
sigmoid(x_t W_g)``, ``y_t = [g_th o_th]_h W_o`` (arXiv:2505.06708, G1);
``rank_rescale``, the normed query latent times ``(width / q_lora_rank)^1/2``
and the normed key-value latent times ``(width / kv_lora_rank)^1/2``
(LongCat-Flash's ``mla_scale_q_lora`` / ``mla_scale_kv_lora``): the cached
line holds the scaled latent. With nothing configured the module lowers to
the program it lowered to before it had any of this
(tests/test_latent_sparse.py), one stream (``hc_mult = 1``) among it.
"""

from __future__ import annotations

import functools
import math
import operator
from typing import NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu import telemetry
from distkeras_tpu.ops.attention import MASK_VALUE
from distkeras_tpu.ops.cache_rows import gather_rows

#: the ``jax.named_scope`` names this file's forward declares (and
#: models/hybrid.py's experts with it): every operation it traces lies
#: under one, and ``profiling/scopes.py`` gives an executable's instruction
#: to the innermost one on its ``op_name`` path. ``attn.latent`` is a
#: layer's attention outside the finer names: its projections, the rotary
#: turn, the absorbed products, the long block's up-projection of the rows
#: it attends; ``attn.latent.square`` the long block's scores, mask,
#: softmax and weighted sum a query block; ``cache.write`` the block's
#: lines, index keys and ring written into the cache; with one residual
#: stream a residual sum goes with the sub-layer whose result it takes.
#: With several (``hc_mult > 1``) the streams' update is the mechanism's
#: own: ``hc.map`` a sub-layer's mapping (norm, projection, sigmoids,
#: Sinkhorn), ``hc.pre`` the mix into its input (and the head's sum of the
#: streams; the compiler fuses it into the norm that reads it, so its time
#: reads as ``norm``'s), ``hc.post`` the update of the streams by ``H_res``
#: and its output
SCOPES = ("embed", "norm", "attn.latent", "attn.latent.square", "attn.index",
          "attn.select", "attn.sparse", "attn.window", "cache.write",
          "moe.route", "moe.experts", "moe.experts.gather",
          "moe.experts.products", "moe.experts.add", "moe.shared",
          "mlp.dense", "head", "hc.map", "hc.pre", "hc.post")

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

#: stretches of queries in which a whole prompt under a selection is
#: attended, each over the rows up to its own end
#: (:func:`_attend_expanded`): 4 multiply 10/16 of the causal square, 8
#: would 9/16, in twice the program
_CAUSAL_STRETCHES = 4

#: most tokens the expert layer puts through every held expert (each
#: token keeping its own gates' part) instead of sorting them by expert.
#: On the v5e at the published widths, 32 held experts, one layer: 256
#: tokens 3.49 ms against 5.99 sorted, 128 tokens 3.07 against 5.95; 1024
#: tokens 11.84 against 6.56 (my chip run, PR 27, the sorted rows then
#: through ``jax.lax.ragged_dot``, ~6 ms however few they were): the
#: masked product reads the same 1.6 GB and multiplies 32 x the rows
_DENSE_MAX_TOKENS = 256

#: places of a block of the many-token expert path: a block is one expert's,
#: so an expert with r tokens costs ceil(r / 256) passes over its matrices.
#: At the published widths a pass reads 10 to 17 MB a matrix (12 to 20 us)
#: and 256 rows multiply in about as long: smaller blocks wait for the
#: weights, larger ones multiply padding. What is laid out in blocks is
#: indices, not rows (:func:`_in_expert_blocks`): the worst case, ``n * k /
#: 256 + held`` blocks, costs 8 bytes a place, and only the blocks in use
#: move a row. Why a loop and not
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

#: the index key's LayerNorm (DeepSeek-V3.2's ``Indexer.k_norm``)
_INDEX_NORM_EPS = 1e-6


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


_unit_normal = nn.initializers.normal(1.0)

_MATRICES = frozenset((
    "q_a", "q_b", "kv_a", "kv_b", "o", "router", "gate", "up", "down",
    "shared_gate", "shared_up", "shared_down", "head",
    "index_q", "index_k", "index_w", "o_gate"))


def param_init(name: str):
    """The initialiser ``(key, shape, dtype)`` of the parameter called
    ``name`` (the last key of its path): the one place that says how this
    family's weights are drawn, for the modules below and for whoever
    makes the weights a leaf at a time (perf/builders/latent_moe.py).
    Norm vectors one, the embedding unit normal, every matrix and stack
    of matrices :func:`_fan_in`, a router's selection bias normal of
    deviation 0.1 (enough to move a choice, as a trained one does), the
    index key's LayerNorm bias zero; a hyper-connection's ``phi_*``
    :func:`_fan_in`, its ``alpha_*`` one and its ``b_*`` unit normal, so
    that ``H_res`` is far from the identity and from the uniform matrix; a
    name it does not know raises."""
    if name == "index_k_bias":
        return nn.initializers.zeros
    if name.endswith("norm") or "_norm_" in name or name.startswith("alpha_"):
        return nn.initializers.ones
    if name == "tok_embed" or name.startswith("b_"):
        return _unit_normal
    if name == "router_bias":
        return nn.initializers.normal(0.1)
    if name in _MATRICES or name.startswith("phi_"):
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


def index_scores(iq, iw, keys, pos):
    """``I_ts`` of the module docstring. ``iq [b, t, heads, dim]`` index
    queries (rotated), ``iw [b, t, heads]`` float32 head weights, ``keys
    [b, R, dim]`` cached index keys, ``pos [b, t]``. The products in the
    keys' type with float32 sums, ``relu``, the heads' weighted sum in
    float32; ``[b, t, R]`` float32 with ``-inf`` at every position past
    ``pos``: what lies there is never chosen."""
    b, t, heads, dim = iq.shape
    r = keys.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", iq.reshape(b, t * heads, dim), keys,
                   preferred_element_type=jnp.float32)
    s = jnp.sum(jax.nn.relu(s).reshape(b, t, heads, r) * iw[..., None],
                axis=2)
    return jnp.where(jnp.arange(r) <= pos[..., None], s, -jnp.inf)


def _kth_largest(image, k: int):
    """The k-th largest value a row of ``image [..., R]`` (unsigned 32-bit),
    exact and without a sort: its bits from the highest down, each kept if
    at least ``k`` values reach the number it makes (32 compare-and-count
    passes)."""
    def narrow(bit, found):
        trial = found | (jnp.uint32(1 << 31) >> jnp.uint32(bit))
        enough = jnp.sum(image >= trial[..., None], axis=-1) >= k
        return jnp.where(enough, trial, found)

    return jax.lax.fori_loop(
        0, 32, narrow, jnp.zeros(image.shape[:-1], jnp.uint32))


def select_mask(scores, k: int):
    """The ``k`` positions of largest score a row as a mask over ``scores
    [..., R]`` (float32, ``-inf`` where nothing may be chosen), ties to the
    lower position: the set a full stable sort would give, found from the
    k-th largest score alone: everything above it, and of the positions
    that tie with it the lowest, as many as there is room for. The k-th
    largest is searched over the floats' ordered integer image (a
    negative's bits flipped, the sign bit of the rest set), not sorted for:
    on the v5e, one prefill query block ``[256, 10240]``, k 2048: 0.28 ms
    against 1.65 for ``jax.lax.top_k``'s last value; ``[256, 8192]``: 0.23
    against 0.98 (my chip run, PR 35, call F; PERF.md section 6)."""
    if scores.shape[-1] <= k:
        return scores > -jnp.inf
    # zeros of both signs tie: one image for both
    bits = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0, 0.0, scores), jnp.int32)
    image = jax.lax.bitcast_convert_type(
        jnp.where(bits < 0, ~bits, bits ^ jnp.int32(-2 ** 31)), jnp.uint32)
    kth = _kth_largest(image, k)[..., None]
    above, tied = image > kth, image == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return (above | (tied & (jnp.cumsum(tied, axis=-1) <= room))) \
        & (scores > -jnp.inf)


#: positions a block of :func:`select_top`'s compaction: a tile's lanes
_SELECT_BLOCK = 128


def select_top(scores, k: int):
    """:func:`select_mask`'s set as positions, for a gather: ``scores [...,
    R]`` -> ``(positions [..., k] int32, ascending, valid [..., k])``;
    ``valid`` is false in the places past the set's size (fewer than ``k``
    positions could be chosen). No sort here either: the mask is counted in
    blocks of :data:`_SELECT_BLOCK` positions, place ``j`` lies in the
    first block whose running count passes ``j`` and, within it, at the
    first position whose count does; a place's block of counts is picked
    by a one-hot product (counts to 128 are exact in bfloat16). On the
    v5e, 32 lanes x 11 264 positions, k 2048: ``jax.lax.top_k`` (a sort
    there) 2.14 ms, this 0.39, the same sets; dots3_note's decode step at
    32 lanes with ``top_k`` in this function's place 20.74 ms, with this
    16.89 (my chip run, PR 35, call F; PERF.md section 6)."""
    blk = _SELECT_BLOCK
    mask = select_mask(scores, k)
    lead, r = mask.shape[:-1], mask.shape[-1]
    blocks = -(-r // blk)
    mask = jnp.pad(mask, [(0, 0)] * len(lead) + [(0, blocks * blk - r)])
    within = jnp.cumsum(mask.reshape(lead + (blocks, blk)), axis=-1,
                        dtype=jnp.int32)                # [..., blocks, blk]
    count = within[..., -1]
    upto = jnp.cumsum(count, axis=-1)                   # [..., blocks]
    place = jnp.arange(k)[:, None]                      # [k, 1]
    passed = upto[..., None, :] <= place                # [..., k, blocks]
    block = jnp.sum(passed, axis=-1, dtype=jnp.int32)
    rank = place[:, 0] - jnp.sum(
        jnp.where(passed, count[..., None, :], 0), axis=-1)
    mine = jnp.einsum(
        "...kb,...bp->...kp",
        (jnp.arange(blocks) == block[..., None]).astype(jnp.bfloat16),
        within.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
    at = block * blk + jnp.sum(mine <= rank[..., None], axis=-1,
                               dtype=jnp.int32)
    return jnp.minimum(at, r - 1), place[:, 0] < upto[..., -1:]


def _attend_expanded(q, rows, pos, w_kvb, dims, scale, index=None,
                     from_zero=False):
    """Long-block form. ``q [b, t, h, nope + rope]`` (rotated), ``rows
    [b, R, line]`` cached lines, ``pos [b, t]``, ``scale [b, t]`` float32
    (softmax scale times the position's factor). Keys and values of all
    ``R`` positions are up-projected per head once; the queries go
    through in blocks. ``index = (iq, iw, keys, k)`` (:func:`index_scores`'
    arguments and the selection's size) puts the selection's mask in the
    causal one's place, a query block at a time. ``from_zero``: the block
    is a whole prompt from its first token (query ``j`` at position ``j``),
    so under ``index`` each of :data:`_CAUSAL_STRETCHES` stretches of
    queries meets only the rows up to its own end, 5/8 of the square: what
    lies past a query is never chosen. Returns ``[b, t, h, v]``."""
    rank, nope, rope, v_dim, heads = dims
    b, t = q.shape[:2]
    r = rows.shape[1]
    if index is not None and from_zero:
        stretch = -(-t // (_CAUSAL_STRETCHES * _QUERY_BLOCK)) * _QUERY_BLOCK
        if stretch < min(t, r):
            return jnp.concatenate([_attend_expanded(
                q[:, lo:lo + stretch], rows[:, :lo + stretch],
                pos[:, lo:lo + stretch], w_kvb, dims,
                scale[:, lo:lo + stretch],
                (index[0][:, lo:lo + stretch], index[1][:, lo:lo + stretch],
                 index[2][:, :lo + stretch], index[3]))
                for lo in range(0, t, stretch)], axis=1)
    kv = jnp.einsum("brc,chd->brhd", rows[..., :rank],
                    w_kvb.reshape(rank, heads, nope + v_dim))
    k_rope = jnp.broadcast_to(rows[:, :, None, rank:rank + rope],
                              (b, r, heads, rope))
    k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)
    v = kv[..., nope:]

    def attend(args):
        q_blk, pos_blk, scale_blk, *index_blk = args  # [b, block, ...]
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k).astype(jnp.float32)
        s = s * scale_blk[:, None, :, None]
        if index is None:
            mask = jnp.arange(r)[None, None, None, :] \
                <= pos_blk[:, None, :, None]
        else:
            with jax.named_scope("attn.index"):
                chosen = index_scores(*index_blk, index[2], pos_blk)
            with jax.named_scope("attn.select"):
                mask = select_mask(chosen, index[3])[:, None]
        p = jax.nn.softmax(jnp.where(mask, s, MASK_VALUE), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)

    with jax.named_scope("attn.latent.square"):
        return in_query_blocks(
            attend, (q, pos, scale) + (() if index is None else index[:2]),
            _QUERY_BLOCK)


def _absorb_query(q, w, dims, line: int, dtype):
    """``q [b, t, h, nope + rope]`` against cached lines: the key
    up-projection ``w [rank, h, nope + v]`` goes into the query (``q_nope
    W^K``, ``rank`` wide, in the lines' ``dtype``), the rotary part follows,
    and zeros meet the padding of a ``line``-wide stored line. ``[b, t, h,
    line]``."""
    rank, nope, rope = dims[:3]
    q_lat = jnp.einsum("bthd,chd->bthc", q[..., :nope], w[..., :nope])
    parts = [q_lat.astype(dtype), q[..., nope:]]
    if line > rank + rope:      # a padded line: zeros meet its padding
        parts.append(jnp.zeros(q.shape[:3] + (line - rank - rope,), q.dtype))
    return jnp.concatenate(parts, axis=-1)


def _attend_absorbed(q, rows, pos, w_kvb, dims, scale, mask=None):
    """Short-block form, same numbers: the key up-projection goes into
    the query (``q_nope W^K``, ``rank`` wide), the scores are taken
    against the cached lines as they lie, the weighted sum of latents is
    up-projected to values after. Arguments and result as
    :func:`_attend_expanded`; ``mask [b, t, R]`` stands in the causal
    mask's place where the rows are not in the order of their positions
    (a ring)."""
    rank, nope, rope, v_dim, heads = dims
    b, t = q.shape[:2]
    r, line = rows.shape[1:]
    w = w_kvb.reshape(rank, heads, nope + v_dim)
    q_line = _absorb_query(q, w, dims, line, rows.dtype).reshape(
        b, t * heads, line)
    s = jnp.einsum("bql,bkl->bqk", q_line, rows).astype(jnp.float32)
    s = s.reshape(b, t, heads, r) * scale[:, :, None, None]
    if mask is None:
        mask = jnp.arange(r)[None, None, None, :] <= pos[:, :, None, None]
    else:
        mask = mask[:, :, None, :]
    p = jax.nn.softmax(jnp.where(mask, s, MASK_VALUE), axis=-1)
    o_lat = jnp.einsum("bqk,bkl->bql",
                       p.astype(rows.dtype).reshape(b, t * heads, r), rows)
    o_lat = o_lat.reshape(b, t, heads, line)[..., :rank]
    return jnp.einsum("bthc,chd->bthd", o_lat, w[..., nope:])


def _attend_selected(q, leaf, rows, pos, w_kvb, dims, scale, index):
    """Short-block form under a selection, the absorbed form's numbers
    over the chosen positions only. ``leaf [n, R, line]`` is the cache leaf
    as it lies and ``rows [b]`` the lanes' rows of it; ``index = (iq, iw,
    keys [b, R, dim], k)``. The ``k`` chosen lines a query are gathered
    from the leaf (``k x line`` values a query, not ``R x line`` a lane);
    a place of the selection that holds nothing (fewer than ``k``
    positions to choose from) is masked in the softmax. Returns ``([b, t,
    h, v], attended [b, t] int32)``."""
    rank, nope, rope, v_dim, heads = dims
    iq, iw, keys, k = index
    with jax.named_scope("attn.index"):
        chosen = index_scores(iq, iw, keys, pos)
    with jax.named_scope("attn.select"):
        at, valid = select_top(chosen, min(k, chosen.shape[-1]))
        lines = leaf[rows[:, None, None], at]               # [b, t, k, line]
    with jax.named_scope("attn.sparse"):
        w = w_kvb.reshape(rank, heads, nope + v_dim)
        q_line = _absorb_query(q, w, dims, leaf.shape[-1], lines.dtype)
        s = jnp.einsum("bthl,btkl->bthk", q_line, lines,
                       preferred_element_type=jnp.float32)
        s = s * scale[:, :, None, None]
        p = jax.nn.softmax(
            jnp.where(valid[:, :, None, :], s, MASK_VALUE), axis=-1)
        o_lat = jnp.einsum("bthk,btkl->bthl", p.astype(lines.dtype),
                           lines)[..., :rank]
        out = jnp.einsum("bthc,chd->bthd", o_lat, w[..., nope:])
    return out, jnp.sum(valid, axis=-1, dtype=jnp.int32)


def _attend_chosen(q, leaf, rows, pos, w_kvb, dims, scale, index,
                   from_zero=False):
    """A block under the selection, in the form its length takes, over the
    lanes' ``rows`` of ``leaf`` (lane i = row i for ``None``): short, the
    chosen lines gathered from the leaf where they lie; long, the expanded
    form over the whole rows (``from_zero``, a whole prompt: over the rows
    up to each stretch of queries) under the selection's mask, where every
    query attends ``min(pos + 1, k)`` positions. Returns ``([b, t, h, v],
    attended [b, t] int32)``."""
    if q.shape[1] <= _ABSORB_MAX_BLOCK:
        if rows is None:
            rows = jnp.arange(q.shape[0])
        return _attend_selected(q, leaf, rows, pos, w_kvb, dims, scale, index)
    out = _attend_expanded(q, gather_rows(leaf, rows), pos, w_kvb, dims,
                           scale, index, from_zero)
    return out, jnp.minimum(pos + 1, index[3]).astype(jnp.int32)


def _lanes_at_a_time(b: int, short: bool, cache_rows) -> int:
    """Lanes a cache call attends at a time: a short block over the pool's
    rows goes in groups of :data:`_LANE_GROUP` where they divide the lanes,
    anything else whole."""
    if short and cache_rows is not None and b % _LANE_GROUP == 0:
        return _LANE_GROUP
    return b


def ring_holds(last, cells: int):
    """The position each cell of a ring of ``cells`` holds once position
    ``last [b]`` is written, position ``p`` lying in cell ``p mod cells``:
    the latest position up to ``last`` of the cell's residue, ``[b,
    cells]``; negative where the cell has never been written. This
    arithmetic on a lane's length is all that says what a ring's cell
    holds: no length mask lies over a ring."""
    return last[:, None] - (last[:, None] - jnp.arange(cells)[None, :]) % cells


def window_mask(held, pos, window: int):
    """Which of the positions ``held [b, R]`` (negative: none) the queries
    at ``pos [b, t]`` attend under a causal window of ``window`` positions
    that counts the query itself: ``pos - window < held <= pos``, ``[b, t,
    R]``."""
    held, pos = held[:, None, :], pos[:, :, None]
    return (held >= 0) & (held <= pos) & (held > pos - window)


def _attend_band(q, lines, pos, w_kvb, dims, scale, window: int):
    """Long-block form under a window: the block attends ITSELF (``lines
    [b, t, line]``, the lines of the block's own positions, so a whole
    prompt from its first token), expanded per head once, and a query block
    attends the band of keys that its window reaches, ``window - 1`` (up to
    whole tiles) before its first query through its last, not the square.
    Arguments otherwise and result as :func:`_attend_expanded`."""
    rank, nope, rope, v_dim, heads = dims
    b, t = q.shape[:2]
    kv = jnp.einsum("brc,chd->brhd", lines[..., :rank],
                    w_kvb.reshape(rank, heads, nope + v_dim))
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(lines[:, :, None, rank:rank + rope],
                          (b, t, heads, rope))], axis=-1)
    block = min(t, _QUERY_BLOCK)
    reach = -(-(window - 1) // 128) * 128
    fit = lambda a: jnp.pad(a, ((0, 0), (reach, -t % block), (0, 0), (0, 0)))
    k, v = fit(k), fit(kv[..., nope:])
    span = jnp.arange(reach + block) - reach

    def attend(args):
        q_blk, at_blk, scale_blk = args               # [b, block, ...]
        first = at_blk[0, 0]
        band = lambda a: jax.lax.dynamic_slice_in_dim(
            a, first, reach + block, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, band(k),
                       preferred_element_type=jnp.float32)
        s = s * scale_blk[:, None, :, None]
        mask = window_mask((first + span)[None, :], at_blk[:1], window)
        p = jax.nn.softmax(jnp.where(mask[:, None], s, MASK_VALUE), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), band(v))

    at = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    return in_query_blocks(attend, (q, at, scale), _QUERY_BLOCK)


def _attend_window(q, lines, pos, w_kvb, dims, scale, window: int,
                   cache, cache_rows, real_len):
    """A block under a causal window, in the form its length takes.
    Without ``cache`` the block attends itself. With ``cache`` (``{"ring":
    [rows, cells, line]}``, position ``p`` in cell ``p mod cells``):

    - a SHORT block writes its lines into the lanes' rings first and
      attends the rings as they lie, absorbed, each cell under the position
      :func:`ring_holds` says it holds. ``cells >= window + t - 1``, so
      that no line a query of the block attends is overwritten by a later
      position of the same block;
    - a LONG block is a whole prompt: it attends itself in a band
      (:func:`_attend_band`) and then builds each row's ring anew from its
      ``real_len [b]`` real positions (all by default): the last ``cells``
      of THOSE, not the bucket's tail. The ring is overwritten whole.

    Returns ``([b, t, h, v], new_cache)``."""
    b, t = q.shape[:2]
    short = t <= _ABSORB_MAX_BLOCK
    if cache is None:
        if not short:
            return _attend_band(q, lines, pos, w_kvb, dims, scale, window), \
                None
        return _attend_absorbed(q, lines, pos, w_kvb, dims, scale,
                                window_mask(pos, pos, window)), None
    leaf = cache["ring"]
    cells, line = leaf.shape[1:]
    if line > lines.shape[-1]:
        lines = jnp.pad(lines, ((0, 0), (0, 0), (0, line - lines.shape[-1])))
    lines = lines.astype(leaf.dtype)
    rows = jnp.arange(b) if cache_rows is None else cache_rows
    if not short:
        out = _attend_band(q, lines, pos, w_kvb, dims, scale, window)
        real = t if real_len is None else real_len
        with jax.named_scope("cache.write"):
            held = ring_holds(pos[:, 0] + real - 1, cells)
            ring = jnp.take_along_axis(
                lines, jnp.clip(held - pos[:, :1], 0, t - 1)[..., None],
                axis=1)
            return out, {"ring": leaf.at[rows].set(ring)}
    if cells < window + t - 1:
        raise ValueError(
            f"a ring of {cells} cells cannot hold a window of {window} "
            f"under a block of {t} positions written at once")
    with jax.named_scope("cache.write"):
        leaf = leaf.at[rows[:, None], pos % cells].set(lines)
    mask = window_mask(ring_holds(pos[:, -1], cells), pos, window)
    lanes = _lanes_at_a_time(b, True, cache_rows)
    out = jnp.concatenate([
        _attend_absorbed(
            q[g:g + lanes],
            gather_rows(leaf, None if cache_rows is None
                        else cache_rows[g:g + lanes]),
            pos[g:g + lanes], w_kvb, dims, scale[g:g + lanes],
            mask[g:g + lanes]) for g in range(0, b, lanes)], axis=0)
    return out, {"ring": leaf}


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
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    window: int = 0
    head_gate: bool = False
    rank_rescale: bool = False

    @nn.compact
    def __call__(self, x, pos, cache=None, cache_rows=None, real_len=None):
        """``x [b, t, width]`` (normed), ``pos [b, t]``. With ``cache``
        (``{"kv": [rows, R, line]}``, and ``"ik": [rows, R, index_dim]``
        under an indexer; ``{"ring": [rows, cells, line]}`` under a window)
        the block's lines are written in place first and ``(out, new_cache,
        attended)`` returns; without, the block attends itself. ``attended
        [b, t]`` int32, the positions each query attended, is ``None``
        without an indexer or a window. ``real_len [b]`` marks a prefill:
        a whole prompt from position 0, that many of its positions real. A
        window's long block builds its rings from the real positions
        (:func:`_attend_window`), and a selection's long block attends in
        stretches from position 0 (``from_zero`` of :func:`_attend_chosen`),
        which only such a block may."""
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
        if self.rank_rescale:
            c_q = c_q * (width / self.q_lora_rank) ** 0.5
        q = (c_q.astype(dtype) @ mat("q_b", self.q_lora_rank,
                                     heads * (nope + rope))
             ).reshape(b, t, heads, nope + rope)
        q = jnp.concatenate(
            [q[..., :nope],
             rope_interleaved(q[..., nope:], pos, inv_freq).astype(dtype)],
            axis=-1)
        kv_a = x @ mat("kv_a", width, rank + rope)
        latent = rms_norm(kv_a[..., :rank], vec("kv_norm", rank),
                          self.rms_eps)
        if self.rank_rescale:
            latent = latent * (width / rank) ** 0.5
        lines = jnp.concatenate(
            [latent.astype(dtype),
             rope_interleaved(kv_a[..., rank:], pos, inv_freq).astype(dtype)],
            axis=-1)
        w_kvb = mat("kv_b", rank, heads * (nope + self.v_head_dim))
        w_o = mat("o", heads * self.v_head_dim, width)
        scale = self.softmax_scale * position_scale(
            pos, self.position_beta, self.original_max_len)
        index = keys = None
        if self.index_heads:
            with jax.named_scope("attn.index"):
                index, keys = self._index(x, c_q.astype(dtype), pos,
                                          inv_freq, mat, vec)
        short = t <= _ABSORB_MAX_BLOCK
        attend = _attend_absorbed if short else _attend_expanded
        new_cache = attended = None
        if self.window:
            with jax.named_scope("attn.window"):
                out, new_cache = _attend_window(
                    q, lines, pos, w_kvb, dims, scale, self.window, cache,
                    cache_rows, real_len)
            attended = jnp.minimum(pos + 1, self.window).astype(jnp.int32)
        elif cache is None and index is None:
            out = attend(q, lines, pos, w_kvb, dims, scale)
        elif cache is None:
            out, attended = _attend_chosen(
                q, lines, None, pos, w_kvb, dims, scale,
                index + (keys, self.index_topk), from_zero=True)
        else:
            leaf = cache["kv"]
            line = leaf.shape[-1]
            if line > lines.shape[-1]:
                lines = jnp.pad(lines, ((0, 0), (0, 0),
                                        (0, line - lines.shape[-1])))
            rows = jnp.arange(b) if cache_rows is None else cache_rows
            # in place, first; mode="drop": the decode step's ghost
            # position past the row's end must not clamp onto its last cell
            with jax.named_scope("cache.write"):
                leaf = leaf.at[rows[:, None], pos].set(
                    lines.astype(leaf.dtype), mode="drop")
                new_cache = {"kv": leaf}
                if index is not None:
                    key_leaf = cache["ik"].at[rows[:, None], pos].set(
                        keys.astype(cache["ik"].dtype), mode="drop")
                    new_cache["ik"] = key_leaf
            lanes = _lanes_at_a_time(b, short, cache_rows)
            group = lambda a, g: None if a is None else a[g:g + lanes]
            if index is None:
                out = jnp.concatenate([
                    attend(q[g:g + lanes],
                           gather_rows(leaf, group(cache_rows, g)),
                           pos[g:g + lanes], w_kvb, dims, scale[g:g + lanes])
                    for g in range(0, b, lanes)], axis=0)
            else:
                with jax.named_scope("attn.index"):
                    keys_of = [gather_rows(key_leaf, group(cache_rows, g))
                               for g in range(0, b, lanes)]
                parts = [_attend_chosen(
                    q[g:g + lanes], leaf, group(cache_rows, g),
                    pos[g:g + lanes], w_kvb, dims, scale[g:g + lanes],
                    (index[0][g:g + lanes], index[1][g:g + lanes],
                     keys_of[g // lanes], self.index_topk),
                    from_zero=real_len is not None)
                    for g in range(0, b, lanes)]
                out = jnp.concatenate([p[0] for p in parts], axis=0)
                attended = jnp.concatenate([p[1] for p in parts], axis=0)
        if self.head_gate:
            gate = jax.nn.sigmoid(
                (x @ mat("o_gate", width, heads)).astype(jnp.float32))
            out = out.astype(jnp.float32) * gate[..., None]
        out = out.reshape(b, t, heads * self.v_head_dim).astype(dtype) @ w_o
        return out, new_cache, attended

    def _index(self, x, c_q, pos, inv_freq, mat, vec):
        """The block's index queries and head weights, ``(iq [b, t, heads,
        dim], iw [b, t, heads] float32)``, and its index keys ``[b, t,
        dim]`` as they are cached: LayerNorm in float32, the rotation on
        the first ``qk_rope_head_dim`` values, then the cache's type."""
        b, t = x.shape[:2]
        heads, dim, rope = self.index_heads, self.index_dim, \
            self.qk_rope_head_dim
        turn = lambda a: jnp.concatenate(
            [rope_interleaved(a[..., :rope], pos, inv_freq),
             a[..., rope:].astype(jnp.float32)], axis=-1).astype(self.dtype)
        iq = turn((c_q @ mat("index_q", self.q_lora_rank, heads * dim)
                   ).reshape(b, t, heads, dim))
        k = (x @ mat("index_k", x.shape[-1], dim)).astype(jnp.float32)
        k = k - jnp.mean(k, axis=-1, keepdims=True)
        k = k * jax.lax.rsqrt(jnp.mean(jnp.square(k), axis=-1, keepdims=True)
                              + _INDEX_NORM_EPS)
        keys = turn(k * vec("index_k_norm", dim) + vec("index_k_bias", dim))
        iw = (x @ mat("index_w", x.shape[-1], heads)).astype(jnp.float32) \
            * (heads ** -0.5 * dim ** -0.5)
        return (iq, iw), keys

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
def _in_expert_blocks(xb, gate, up, down, group, top):
    """The many-token expert product: the tokens' routed sum, ``[n, d]``
    float32. ``xb [n, d]`` tokens, ``gate`` (or ``None``: the ungated
    form), ``up [held, d, width]``, ``down [held, width, d]``, ``group [n *
    k]`` each assignment's held expert (``held`` for an absent one, which
    sorts behind every group and gets no place), ``top [n, k]`` float32
    the assignments' weights. Only indices are laid out: the held
    assignments, sorted by expert, fill blocks of :data:`_EXPERT_BLOCK`
    places, each block one expert's, and every place carries the token it
    reads and the weight it adds with (a block's padding: a spare row
    past the tokens, weight 0), 8 bytes a place whatever the routing. A
    loop runs over the blocks in use: a block gathers its rows from ``xb``,
    takes two or three products against that expert's matrices where they
    lie, rounds as the matrices are typed, weighs in float32 and adds into
    the tokens' sum at the same indices, which within a block are distinct
    and ascending (a token chooses an expert once and the sort is stable).
    No row is moved for an assignment this chip does not hold. Jitted so
    that a model's expert layers share one trace and one lowered function
    a shape.

    The function alone on the v5e (one layer's routed sum, even routing,
    ms; my chip runs, PR 36), the parent's, which laid out rows (``[n * k
    + held * 256, d]`` written, carried through the loop and gathered
    back), beside this one: ``n`` 8192 and 10 240, ``d`` 5120, width 1536,
    32 held of 256, k 8: 56.0 -> 14.4 and 69.3 -> 18.4; 2048 and 4096,
    4096, 2048, 32 of 128, k 4: 6.20 -> 5.25 and 8.66 -> 5.51; 2048 and
    4096, 2688, 1856 ungated, 64 of 128, k 6: 6.2 -> 5.92 and 8.9 -> 6.20.
    Of the 14.4: the products 6.7, the rows gathered 1.0, the adds 6.7
    (0.55 us a row). With ``indices_are_sorted=True`` promised to the add
    as well the six read 73.0, 109.0, 8.0, 12.2, 7.4 and 10.3: slower than
    the parent, so it is not promised."""
    blk, dtype = _EXPERT_BLOCK, xb.dtype
    held, n = up.shape[0], xb.shape[0]
    k = group.size // n
    sizes = jnp.sum(group[:, None] == jnp.arange(held)[None, :],
                    axis=0, dtype=jnp.int32)
    order = jnp.argsort(group, stable=True)
    blocks_of = -(-sizes // blk)
    ends = jnp.cumsum(blocks_of)
    n_blocks = order.size // blk + held   # sum of ceil(size / blk), at most
    expert_of = jnp.minimum(jnp.searchsorted(
        ends, jnp.arange(n_blocks), side="right"), held - 1)
    # a place's rank among its expert's sorted assignments, [n_blocks, blk]
    rank = ((jnp.arange(n_blocks) - (ends - blocks_of)[expert_of]) * blk
            )[:, None] + jnp.arange(blk)
    real = rank < sizes[expert_of][:, None]
    source = order[jnp.minimum(
        (jnp.cumsum(sizes) - sizes)[expert_of][:, None] + rank,
        order.size - 1)]
    token = jnp.where(real, source // k, n + jnp.arange(blk))
    weight = jnp.where(real, top.reshape(-1)[source], 0.0)

    def one_block(b, total):
        mine = lambda w: jax.lax.dynamic_index_in_dim(
            w, expert_of[b], keepdims=False)
        with jax.named_scope("moe.experts.gather"):
            x_b = xb.at[token[b]].get(mode="clip", indices_are_sorted=True)
        with jax.named_scope("moe.experts.products"):
            wide = lambda w: jnp.dot(
                x_b, mine(w), preferred_element_type=jnp.float32)
            h = jax.nn.silu(wide(gate)) * wide(up) if gate is not None \
                else _relu2(wide(up))
            out = jnp.dot(h.astype(dtype), mine(down),
                          preferred_element_type=jnp.float32).astype(dtype)
        # distinct is promised, ascending is not: told that its indices are
        # sorted, the chip's scatter took 5 times as long (docstring)
        with jax.named_scope("moe.experts.add"):
            return total.at[token[b]].add(
                out.astype(jnp.float32) * weight[b][:, None],
                unique_indices=True)

    total = jnp.zeros((n + blk, xb.shape[1]), jnp.float32)
    return jax.lax.fori_loop(0, ends[-1], one_block, total)[:n]


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
        gated = self.activation == "swiglu"
        gate = mat("gate", held, d, self.width) if gated else None
        up = mat("up", held, d, self.width)
        down = mat("down", held, self.width, d)
        with jax.named_scope("moe.experts"):
            xb = x.astype(dtype)
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
                # many tokens: each held assignment through its own expert
                group = jnp.where(here, local, held).reshape(-1)   # [n * k]
                y = _in_expert_blocks(xb, gate, up, down, group, top)
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


class DenseMLP(nn.Module):
    """A leading dense layer's SwiGLU, ``down(silu(gate x) * up x)``."""
    width: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        mat = lambda name, *shape: self.param(name, param_init(name), shape,
                                              self.dtype)
        return _swiglu(x.astype(self.dtype), mat("gate", d, self.width),
                       mat("up", d, self.width), mat("down", self.width, d))


class HyperConnection(nn.Module):
    """One sub-layer's mapping of ``streams`` residual streams (module
    docstring; mHC's equations 7 to 9): ``x [b, t, streams * width]``
    float32 -> ``(pre [n, b, t], post [n, b, t], res [n, n, b, t])``,
    ``H_pre``, ``H_post`` and ``H_res`` with the tokens last, so that the
    Sinkhorn iterations' sums over rows and columns are sums of whole
    arrays of tokens. Float32; the one product at ``precision=HIGHEST``."""
    streams: int
    sinkhorn_iters: int
    eps: float
    clamp: Tuple[float, float]
    rms_eps: float

    @nn.compact
    def __call__(self, x):
        n = self.streams
        b, t, wide = x.shape
        f32 = jnp.float32
        vec = lambda name, *shape: self.param(name, param_init(name), shape,
                                              f32)
        telemetry.counter("model.hc.sublayers").inc()
        with jax.named_scope("hc.map"):
            v = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                           keepdims=True) + self.rms_eps)
            phi = jnp.concatenate([vec("phi_pre", wide, n),
                                   vec("phi_post", wide, n),
                                   vec("phi_res", wide, n * n)], axis=1)
            a = jnp.einsum("btc,ck->kbt", v, phi,
                           precision=jax.lax.Precision.HIGHEST)
            bias = lambda name, *shape: vec(name, *shape).reshape(-1, 1, 1)
            pre = jax.nn.sigmoid(vec("alpha_pre") * a[:n]
                                 + bias("b_pre", n))
            post = 2.0 * jax.nn.sigmoid(vec("alpha_post") * a[n:2 * n]
                                        + bias("b_post", n))
            res = vec("alpha_res") * a[2 * n:] + bias("b_res", n, n)
            m = jnp.exp(jnp.clip(res, *self.clamp)).reshape(n, n, b, t)
            for _ in range(self.sinkhorn_iters):
                m = m / (jnp.sum(m, axis=0, keepdims=True) + self.eps)
                m = m / (jnp.sum(m, axis=1, keepdims=True) + self.eps)
        return pre, post, m


def _streams(x, n: int):
    """The ``n`` streams of ``x [b, t, n * width]``, each ``[b, t,
    width]``."""
    c = x.shape[-1] // n
    return [x[..., i * c:(i + 1) * c] for i in range(n)]


def _add(parts):
    """``parts[0] + parts[1] + ...``, in that order."""
    return functools.reduce(operator.add, parts)


def _merged(x, n: int):
    """HC's final sum of the ``n`` streams of ``x``: what the head reads."""
    return _add(_streams(x, n))


class WindowSizes(NamedTuple):
    """A window layer's own sizes: the causal window (it counts the query
    itself), the cells of the ring that holds it (at least ``window +
    _ABSORB_MAX_BLOCK - 1``; whole tiles of positions), and its latent
    attention's heads, ranks, head sizes and rotary base."""
    window: int
    ring: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float


def _line(rank: int, rope: int) -> int:
    """A cached line's values: latent and rotary key, padded up to whole
    128-lane tiles (the form the TPU stores as written)."""
    return -(-(rank + rope) // 128) * 128


class LatentMoELM(nn.Module):
    """The decoder. Sizes are those a chip holds: ``vocab_size`` rows of
    the vocabulary (ids, logits and argmax are over that slice) and
    ``num_experts / expert_share[1]`` experts a layer; ``num_experts`` and
    ``experts_per_token`` are the router's published width and top-k. The
    first ``dense_layers`` layers carry a SwiGLU of ``dense_width`` in the
    experts' place; ``scoring`` is :class:`ExpertShare`'s; ``index_heads >
    0`` gives every full layer's attention its indexer (module docstring).
    ``layer_kinds`` says layer by layer which attention it carries: ``"F"``
    full (the sizes above, under the indexer if there is one), ``"S"`` a
    window layer at ``window_sizes`` whose cache is a ring; empty: every
    layer ``"F"``. ``head_gate`` and ``rank_rescale`` are every layer's
    (module docstring). ``hc_mult > 1`` makes the residual that many
    streams under manifold-constrained hyper-connections (module
    docstring): ``hc_sinkhorn_iters`` iterations, ``hc_eps`` on each sum,
    ``hc_clamp`` the interval ``H_res``'s logits are clipped to."""
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
    dense_layers: int = 0
    dense_width: int = 0
    scoring: str = "softmax"
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    layer_kinds: Tuple[str, ...] = ()
    window_sizes: Optional[WindowSizes] = None
    head_gate: bool = False
    rank_rescale: bool = False
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)

    @property
    def experts_held(self) -> int:
        return self.num_experts // self.expert_share[1]

    @property
    def kinds(self) -> Tuple[str, ...]:
        kinds = self.layer_kinds or ("F",) * self.num_layers
        if len(kinds) != self.num_layers or set(kinds) - {"F", "S"} \
                or ("S" in kinds) != (self.window_sizes is not None):
            raise ValueError(
                f"layer_kinds {kinds!r} must name {self.num_layers} layers "
                f"'F' or 'S', and 'S' goes with window_sizes")
        return kinds

    @property
    def cache_select_leaves(self) -> Tuple[str, ...]:
        """The cache leaves a selection reads before any mask (the cache
        protocol, DESIGN.md section 14): the index keys."""
        return ("ik",) if self.index_heads else ()

    @property
    def cache_state_leaves(self) -> Tuple[str, ...]:
        """The cache leaves without a ``max_len`` axis (DESIGN.md section
        14): a window layer's ring, which holds ``ring`` positions however
        long the context, is overwritten whole at admission and is hidden
        by no length mask."""
        return ("ring",) if "S" in self.kinds else ()

    @property
    def cache_line(self) -> int:
        """Values a cached position holds a full layer: the latent and the
        one rotary key, padded up to whole 128-lane tiles (the form the
        TPU stores as written; tests/test_decode_layout.py)."""
        return _line(self.kv_lora_rank, self.qk_rope_head_dim)

    def init_cache(self, batch: int, dtype=None, positions=None):
        """Zeroed cache for ``batch`` rows, a tuple with one entry a layer:
        ``"F"`` ``{"kv": [batch, max_len, cache_line]}`` (``positions`` in
        ``max_len``'s place, where a prefill asks for its fresh row) and,
        under an indexer, ``"ik": [batch, max_len, index_dim]``, the index
        keys; ``"S"`` ``{"ring": [batch, ring, its line]}`` whatever
        ``positions``; in ``dtype`` (the model's own by default)."""
        dtype = dtype or self.dtype
        rows = (batch, positions or self.max_len)
        widths = {"kv": self.cache_line}
        if self.index_heads:
            widths["ik"] = self.index_dim
        w = self.window_sizes
        fresh = {
            "F": lambda: {name: jnp.zeros(rows + (width,), dtype)
                          for name, width in widths.items()},
            "S": lambda: {"ring": jnp.zeros(
                (batch, w.ring, _line(w.kv_lora_rank, w.qk_rope_head_dim)),
                dtype)}}
        return tuple(fresh[kind]() for kind in self.kinds)

    def cache_bytes_per_row(self, dtype=None) -> int:
        row = jax.eval_shape(lambda: self.init_cache(1, dtype))
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree.leaves(row))

    def prefill_row_len(self, block: int) -> int:
        """Positions of the fresh row a ``block``-token prefill writes
        and attends: the block's own, no more."""
        return block

    def _attention(self, kind: str, name: str) -> LatentAttention:
        """Layer ``name``'s attention at its kind's sizes: plain rotary
        frequencies are the YaRN path at factor 1."""
        z = self if kind == "F" else self.window_sizes
        inv_freq = tuple(float(f) for f in yarn_inv_freq(
            z.qk_rope_head_dim, z.rope_theta, self.rope_factor,
            self.rope_beta_fast, self.rope_beta_slow,
            self.rope_original_max_len))
        scale = softmax_scale(z.qk_nope_head_dim + z.qk_rope_head_dim,
                              self.rope_factor, self.rope_mscale_all_dim)
        own = dict(index_heads=self.index_heads, index_dim=self.index_dim,
                   index_topk=self.index_topk) if kind == "F" \
            else dict(window=z.window)
        return LatentAttention(
            z.num_heads, z.q_lora_rank, z.kv_lora_rank, z.qk_nope_head_dim,
            z.qk_rope_head_dim, z.v_head_dim, self.rms_eps, inv_freq, scale,
            self.position_beta, self.rope_original_max_len, self.dtype,
            head_gate=self.head_gate, rank_rescale=self.rank_rescale,
            name=name, **own)

    @nn.compact
    def __call__(self, input_ids, train: bool = False, cache=None,
                 cache_index=None, page_table=None, cache_rows=None,
                 real_len=None):
        """Logits ``[b, t, vocab]`` without ``cache``; with it ``(logits,
        new_cache, routed)`` and, for a model under a selection (it
        declares ``cache_select_leaves``), ``attended [layers, b, t]``
        int32, the positions each query attended a layer (a window layer:
        its window's). ``real_len [b]`` says the call is a prefill
        (``serving.generation.make_prefill_fn``, every family): a whole
        prompt written from position 0 into fresh rows, its first
        ``real_len`` positions real. The attention takes it so
        (:meth:`LatentAttention.__call__`), and the logits are cut to those
        of position ``real_len - 1``, ``[b, 1, vocab]``: the streams' sum,
        the final norm and the head run on that row alone."""
        del train                       # no dropout: serving only
        if page_table is not None:
            raise ValueError(
                "LatentMoELM keeps one latent line a position and has no "
                "paged form; serve it from the rectangular KVCachePool")
        embed = self.param("tok_embed", param_init("tok_embed"),
                           (self.vocab_size, self.width), self.dtype)
        with jax.named_scope("embed"):
            ids = input_ids.astype(jnp.int32)
            b, t = ids.shape
            if cache is None:
                pos = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
            else:
                pos = cache_index[:, None] + jnp.arange(t)[None, :]
            x = embed[ids].astype(jnp.float32)
            n = self.hc_mult
            if n > 1:                   # the embedding in every stream
                x = jnp.tile(x, (1, 1, n))

        @jax.named_scope("norm")
        def norm(name, a):
            return rms_norm(
                a, self.param(name, param_init(name), (self.width,),
                              jnp.float32), self.rms_eps)

        def enter(name, x):
            """A sub-layer's input and the mapping around it (``None``
            with one stream: the input is the residual itself)."""
            if n == 1:
                return x, None
            hc = HyperConnection(n, self.hc_sinkhorn_iters, self.hc_eps,
                                 self.hc_clamp, self.rms_eps, name=name)(x)
            with jax.named_scope("hc.pre"):
                return _add([w[..., None] * s for w, s in
                             zip(hc[0], _streams(x, n))]), hc

        def leave(x, y, hc):
            """The residual after a sub-layer's output ``y``: its sum, or
            with several streams ``x_i <- sum_j H_res[i, j] x_j + H_post[i]
            y``."""
            if hc is None:
                return x + y.astype(jnp.float32)
            _, post, res = hc
            y = y.astype(jnp.float32)
            with jax.named_scope("hc.post"):
                old = _streams(x, n)
                return jnp.concatenate([
                    _add([res[i, j][..., None] * s for j, s in enumerate(old)])
                    + post[i][..., None] * y for i in range(n)], axis=-1)

        new_cache, routed, attended = [], [], []
        for i, kind in enumerate(self.kinds):
            h, hc = enter(f"hc_attn_{i}", x)
            with jax.named_scope("attn.latent"):
                y, layer_cache, chosen = self._attention(kind, f"attn_{i}")(
                    norm(f"attn_norm_{i}", h), pos,
                    None if cache is None else cache[i], cache_rows,
                    real_len)
                x = leave(x, y, hc)
            new_cache.append(layer_cache)
            attended.append(chosen)
            h, hc = enter(f"hc_mlp_{i}", x)
            if i < self.dense_layers:
                with jax.named_scope("mlp.dense"):
                    x = leave(x, DenseMLP(self.dense_width, self.dtype,
                                          name=f"mlp_{i}")(
                        norm(f"mlp_norm_{i}", h)), hc)
                continue
            y, sent = ExpertShare(
                self.moe_width, self.num_experts, self.experts_per_token,
                self.expert_share, self.routed_scaling, self.dtype,
                self.scoring, name=f"moe_{i}")(
                    norm(f"moe_norm_{i}", h).reshape(b * t, self.width))
            with jax.named_scope("moe.shared"):
                x = leave(x, y.reshape(b, t, self.width), hc)
            with jax.named_scope("moe.route"):
                routed.append(sent.reshape(b, t, -1))
        if real_len is not None:        # the one position a prefill returns
            with jax.named_scope("head"):
                x = jnp.take_along_axis(
                    x, (real_len - 1)[:, None, None], axis=1)
        if n > 1:                       # a row's streams, summed on the row
            with jax.named_scope("hc.pre"):
                x = _merged(x, n)
        with jax.named_scope("head"):
            head = self.param("head", param_init("head"),
                              (self.width, self.vocab_size), self.dtype)
            logits = jnp.dot(norm("final_norm", x).astype(self.dtype), head,
                             preferred_element_type=jnp.float32)
        if cache is None:
            return logits
        with jax.named_scope("moe.route"):
            routed = jnp.stack(routed)
        if not self.cache_select_leaves:
            return logits, tuple(new_cache), routed
        with jax.named_scope("attn.select"):
            return logits, tuple(new_cache), routed, jnp.stack(attended)


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
