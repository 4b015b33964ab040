"""A decoder assembled from a tuple of layer kinds, held as one chip's share.

Each block is ONE mixer and nothing else, ``x <- x + Mixer(RMSNorm(x))``,
its kind the block's letter in ``pattern`` (the ``hybrid_override_pattern``
of the ``nemotron_h`` family):

- ``M``, **a Mamba-2 state-space layer** (arXiv:2405.21060). ``[z | xBC |
  dt] = W_in u``; ``xBC <- silu(conv1d(xBC))``, causal and depthwise over
  ``conv_kernel`` positions; ``xBC -> x [H, P], B [G, N], C [G, N]`` (head
  ``h`` reads group ``h // (H / G)``); ``Delta = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; ``h_t = exp(Delta_t A) h_{t-1} + Delta_t x_t (x)
  B_t``; ``y_t = h_t C_t + D x_t``; ``y <- RMSNorm over groups of d_inner
  / G of (y * silu(z)) * w``; out ``W_out y``. A long block computes it
  chunk by chunk (the SSD form: inside a chunk a masked product, between
  chunks the carried state), a short block (:data:`_STEP_MAX_BLOCK`, the
  decode step) applies the recurrence a position at a time.
- ``*``, **grouped-query attention without a positional term**: ``q, k, v,
  o`` without bias, ``num_heads`` query heads on ``num_kv_heads`` key/value
  heads, scores ``q . k / sqrt(head_dim)``, causal softmax. Position
  enters the model through the state-space layers alone.
- ``E``, **routed experts**: :class:`latent_moe.ExpertShare` scored by a
  sigmoid with a selection bias, experts ``down(relu(up x)^2)`` without a
  gate, and a shared expert of its own width.

Parameters are ``dtype`` (bfloat16 as served) and so are the matrix
products; the vectors (norms, ``dt_bias``, ``A_log``, ``D``, the
convolution's taps, the router) are float32, and the router's product,
every norm, the attention softmax, ``Delta``, ``exp(Delta A)``, the
recurrent state, the residual stream and the logits are float32
(NUMERICS.md "State-space layer").

Cache contract (DESIGN.md section 14). The model owns its cache and
declares TWO kinds of leaf. A ``*`` block keeps ``{"k", "v"}``, ``[rows,
positions, kv width]``: rows x positions, hidden by the length mask, as
the other families'. An ``M`` block keeps ``{"ssm": [rows, H, P, N]
float32, "conv": [rows, conv_kernel - 1, conv_dim]}``: a state a row with
no position axis, which no mask hides (:attr:`HybridLM.cache_state_leaves`
names them; what the engine promises each kind is in DESIGN.md). An ``E``
block keeps nothing (``{}``). A prefill tells a model of every family how
many of its block's positions are real (``real_len [b]``;
``serving.generation.make_prefill_fn``); a cache call without it takes
every one. Past it ``Delta = 0``, which leaves ``h`` exactly as it was,
and the convolution's tail is the last real inputs. With ``real_len`` the
logits are those of position ``real_len - 1`` alone, ``[b, 1, vocab]``,
as every family's: a prefill needs one row of the head, not its bucket's.
A cache call returns ``(logits, new_cache, routed)`` as
:class:`latent_moe.LatentMoELM` does, ``routed [E blocks, b, t,
experts_held]``.
"""

from __future__ import annotations

import math
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.models import latent_moe
from distkeras_tpu.models.latent_moe import ExpertShare, rms_norm
from distkeras_tpu.ops.attention import MASK_VALUE
from distkeras_tpu.ops.cache_rows import gather_rows

#: longest block that takes the recurrence a position at a time; a longer
#: one takes the chunked scan
_STEP_MAX_BLOCK = 4

#: the ``jax.named_scope`` names this file's forward declares, the experts'
#: (models/latent_moe.py) among them: every operation it traces lies under
#: one (``profiling/scopes.py``). ``ssm.step`` holds a decode step's pass
#: over the pooled states, which is their write as well; ``cache.write``
#: the other writes: a prefill's state and tail into their rows, K and V
#: lines; a residual sum goes with the sub-layer whose result it takes
SCOPES = ("embed", "norm", "ssm.in", "ssm.conv", "ssm.scan", "ssm.step",
          "ssm.out", "attn.gqa", "cache.write") + tuple(
              name for name in latent_moe.SCOPES if name.startswith("moe.")
          ) + ("head",)

#: queries a long block attends at a time, lanes a short block attends at
#: a time: latent_moe's, for its reasons
_QUERY_BLOCK = latent_moe._QUERY_BLOCK
_LANE_GROUP = latent_moe._LANE_GROUP

#: the range the initial ``Delta`` is drawn from, and its floor (the
#: source's ``time_step_min``, ``time_step_max``, ``time_step_floor``:
#: they shape the initial ``dt_bias`` and nothing else), and that of ``A``
#: (Mamba-2's ``A_init_range``)
_DT_RANGE, _DT_FLOOR, _A_RANGE = (1e-3, 1e-1), 1e-4, (1.0, 16.0)


def _dt_bias_init(key, shape, dtype):
    """``softplus^-1`` of a ``Delta`` log-uniform over :data:`_DT_RANGE`."""
    lo, hi = (math.log(v) for v in _DT_RANGE)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                                lo, hi)), _DT_FLOOR)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                      *_A_RANGE)).astype(dtype)


_OWN = {"dt_bias": _dt_bias_init, "A_log": _a_log_init,
        "D": nn.initializers.ones, "conv_b": nn.initializers.normal(0.1),
        "in_proj": latent_moe._fan_in, "out_proj": latent_moe._fan_in,
        "conv_w": latent_moe._fan_in, "q": latent_moe._fan_in,
        "k": latent_moe._fan_in, "v": latent_moe._fan_in}


def param_init(name: str):
    """The initialiser ``(key, shape, dtype)`` of the parameter called
    ``name``: this family's own names here, the rest
    :func:`latent_moe.param_init`'s (norm vectors one, the embedding unit
    normal, matrices normal with variance 1 / fan-in)."""
    return _OWN[name] if name in _OWN else latent_moe.param_init(name)


def ssd_scan(x, dt, a, b_mat, c_mat, h0, chunk: int):
    """The chunked (SSD) form of ``h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x)
    B_t``, ``y_t = h_t C_t``. ``x [b, t, G, R, P]``, ``dt [b, t, G, R]``
    float32 (zero where a position is not real: such a position leaves
    ``h`` exactly as it was), ``a [G, R]`` float32 (negative), ``b_mat,
    c_mat [b, t, G, N]``, ``h0 [b, G, R, P, N]`` float32. Returns ``(y [b,
    t, G, R, P] float32, h_t [b, G, R, P, N] float32)``. Products take
    operands in ``x.dtype`` and sum in float32; decays, ``dt`` and the
    carried state are float32."""
    f32, dtype = jnp.float32, x.dtype
    b, t = x.shape[:2]
    q = min(chunk, t)
    pad = -t % q
    c = (t + pad) // q
    chunks = lambda v: jnp.pad(
        v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)).reshape(
            (b, c, q) + v.shape[2:])
    xs, bs, cs = chunks(x), chunks(b_mat), chunks(c_mat)
    dts = jnp.moveaxis(chunks(dt), 2, -1)                  # [b, c, G, R, q]
    cum = jnp.cumsum(dts * a[:, :, None], axis=-1)         # through s
    # inside a chunk: position l reads s <= l through C_l . B_s, the decay
    # between them and dt_s
    cb = jnp.einsum("bclgn,bcsgn->bcgls", cs, bs, preferred_element_type=f32)
    causal = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    decay = jnp.exp(jnp.where(causal, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                   # [b,c,G,R,l,s]
    w = cb[:, :, :, None] * decay * dts[..., None, :]
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", w.astype(dtype), xs,
                   preferred_element_type=f32)
    # what each chunk adds to the state by its end, and how much of the
    # state that entered it is left
    to_end = jnp.moveaxis(jnp.exp(cum[..., -1:] - cum) * dts, -1, 2)
    added = jnp.einsum("bcsgrp,bcsgn->bcgrpn",
                       (xs.astype(f32) * to_end[..., None]).astype(dtype),
                       bs, preferred_element_type=f32)
    kept = jnp.exp(cum[..., -1])                           # [b, c, G, R]

    def carry(h, chunk_of):
        more, left = chunk_of
        return left[..., None, None] * h + more, h

    h_t, entered = jax.lax.scan(
        carry, h0, (jnp.moveaxis(added, 1, 0), jnp.moveaxis(kept, 1, 0)))
    since = jnp.moveaxis(jnp.exp(cum), -1, 2)              # [b, c, q, G, R]
    y = y + jnp.einsum("bclgn,bcgrpn->bclgrp", cs,
                       jnp.moveaxis(entered, 0, 1).astype(dtype),
                       preferred_element_type=f32) * since[..., None]
    return y.reshape((b, t + pad) + x.shape[2:])[:, :t], h_t


def ssm_step_in_pool(x, dt, a, b_mat, c_mat, pool, rows):
    """One position of the recurrence for lanes whose states lie in rows
    ``rows [b]`` of ``pool [R, G, R', P, N]``, computed over the WHOLE pool
    where it lies: every row is multiplied by its decay and given its
    addend, 1 and 0 for a row no lane names, which leaves it exactly as it
    was. One pass reads and writes each state once; gathering the lanes'
    rows, advancing them and scattering them back moves each three times.
    ``x [b, G, R', P]``, ``dt [b, G, R']``, ``b_mat, c_mat [b, G, N]``.
    Returns ``(y [b, G, R', P] float32, new_pool)``. Lanes that name one row
    twice (padding, on the scratch row) leave it one of their states."""
    f32 = jnp.float32
    spread = lambda v, fill: jnp.full(
        (pool.shape[0],) + v.shape[1:], fill, f32).at[rows].set(v.astype(f32))
    new = spread(jnp.exp(dt * a), 1.0)[..., None, None] * pool \
        + spread(dt[..., None] * x.astype(f32), 0.0)[..., None] \
        * spread(b_mat, 0.0)[:, :, None, None, :]
    y = jnp.sum(new * spread(c_mat, 0.0)[:, :, None, None, :], axis=-1)
    return y[rows], new


#: a step takes the whole pool (:func:`ssm_step_in_pool`) when its lanes
#: are at least this share of the pool's rows: three moves a lane against
#: one a row
_IN_POOL_MIN_SHARE = 1 / 3


def ssm_steps(x, dt, a, b_mat, c_mat, h0):
    """The same recurrence a position at a time, for a block of a few
    positions (arguments and result as :func:`ssd_scan`)."""
    f32 = jnp.float32
    h, ys = h0, []
    for j in range(x.shape[1]):
        dt_j = dt[:, j]                                       # [b, G, R]
        h = jnp.exp(dt_j * a)[..., None, None] * h \
            + (dt_j[..., None] * x[:, j].astype(f32))[..., None] \
            * b_mat[:, j].astype(f32)[:, :, None, None, :]
        ys.append(jnp.sum(h * c_mat[:, j].astype(f32)[:, :, None, None, :],
                          axis=-1))
    return jnp.stack(ys, axis=1), h


class Mamba2Mixer(nn.Module):
    heads: int
    head_dim: int
    groups: int
    state: int
    conv_kernel: int
    chunk: int
    rms_eps: float
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, u, cache=None, cache_rows=None, real_len=None):
        """``u [b, t, width]`` (normed, float32). With ``cache`` (``{"ssm",
        "conv"}``) the lanes' state rows are read, advanced by the block's
        first ``real_len`` positions and written back, and ``(out,
        new_cache)`` returns; without, the block starts from a zero state."""
        f32, dtype = jnp.float32, self.dtype
        b, t, width = u.shape
        heads, p_dim, groups, n = (self.heads, self.head_dim, self.groups,
                                   self.state)
        per = heads // groups
        inner, taps = heads * p_dim, self.conv_kernel
        conv_dim = inner + 2 * groups * n
        mat = lambda name, *shape: self.param(name, param_init(name), shape,
                                              dtype)
        vec = lambda name, *shape: self.param(name, param_init(name), shape,
                                              f32)
        with jax.named_scope("ssm.in"):
            rows = None if cache is None else (
                jnp.arange(b) if cache_rows is None else cache_rows)
            if real_len is None:
                real_len = jnp.full((b,), t, jnp.int32)
            proj = jnp.dot(u.astype(dtype),
                           mat("in_proj", width, inner + conv_dim + heads),
                           preferred_element_type=f32)
            z = proj[..., :inner]
            xbc = proj[..., inner:inner + conv_dim].astype(dtype)
            real = jnp.arange(t)[None, :, None] < real_len[:, None, None]
            dt = jnp.where(real, jax.nn.softplus(
                proj[..., inner + conv_dim:] + vec("dt_bias", heads)), 0.0)
        with jax.named_scope("ssm.conv"):
            tail = jnp.zeros((b, taps - 1, conv_dim), dtype) if cache is None \
                else cache["conv"][rows].astype(dtype)
            window = jnp.concatenate([tail, xbc], axis=1)
            w_conv = vec("conv_w", taps, conv_dim)
            xbc = vec("conv_b", conv_dim) + sum(
                w_conv[k] * window[:, k:k + t].astype(f32)
                for k in range(taps))
            xbc = jax.nn.silu(xbc).astype(dtype)
            # the last real inputs: position p lies at window[p + taps - 1]
            new_tail = jnp.take_along_axis(
                window, (real_len[:, None]
                         + jnp.arange(taps - 1)[None, :])[:, :, None], axis=1)
            x = xbc[..., :inner].reshape(b, t, groups, per, p_dim)
            b_mat = xbc[..., inner:inner + groups * n].reshape(
                b, t, groups, n)
            c_mat = xbc[..., inner + groups * n:].reshape(b, t, groups, n)
            a = -jnp.exp(vec("A_log", heads)).reshape(groups, per)
            dt = dt.reshape(b, t, groups, per)
        grouped = (groups, per, p_dim, n)
        new_ssm = None
        if cache is not None and t == 1 \
                and b >= _IN_POOL_MIN_SHARE * cache["ssm"].shape[0]:
            with jax.named_scope("ssm.step"):
                y, new_ssm = ssm_step_in_pool(
                    x[:, 0], dt[:, 0], a, b_mat[:, 0], c_mat[:, 0],
                    cache["ssm"].reshape((-1,) + grouped), rows)
                y = y[:, None]
        else:
            with jax.named_scope(
                    "ssm.step" if t <= _STEP_MAX_BLOCK else "ssm.scan"):
                h0 = jnp.zeros((b,) + grouped, f32) if cache is None \
                    else cache["ssm"][rows].reshape((b,) + grouped)
                if t <= _STEP_MAX_BLOCK:
                    y, h = ssm_steps(x, dt, a, b_mat, c_mat, h0)
                else:
                    y, h = ssd_scan(x, dt, a, b_mat, c_mat, h0, self.chunk)
            if cache is not None:
                with jax.named_scope("cache.write"):
                    new_ssm = cache["ssm"].at[rows].set(
                        h.reshape(b, heads, p_dim, n))
        with jax.named_scope("ssm.out"):
            y = y + vec("D", heads).reshape(groups, per)[..., None] \
                * x.astype(f32)
            y = y.reshape(b, t, groups, inner // groups) \
                * jax.nn.silu(z).reshape(b, t, groups, inner // groups)
            y = y * jax.lax.rsqrt(
                jnp.mean(jnp.square(y), axis=-1, keepdims=True) + self.rms_eps)
            y = y.reshape(b, t, inner) * vec("gate_norm", inner)
            out = jnp.dot(y.astype(dtype), mat("out_proj", inner, width),
                          preferred_element_type=f32)
        if cache is None:
            return out, None
        with jax.named_scope("cache.write"):
            return out, {
                "ssm": new_ssm.reshape(cache["ssm"].shape),
                "conv": cache["conv"].at[rows].set(
                    new_tail.astype(cache["conv"].dtype))}


def _attend_grouped(q, k_rows, v_rows, pos):
    """``q [b, t, KV, R, hd]`` at positions ``pos [b, t]`` over cached lines
    ``k_rows, v_rows [b, r, KV * hd]``: key ``p`` is visible to query ``j``
    iff ``p <= pos[j]``. Float32 scores and softmax; a long block takes its
    queries in blocks of :data:`_QUERY_BLOCK`. Returns ``[b, t, KV, R,
    hd]`` in the values' dtype."""
    b, t, kv, _, hd = q.shape
    r = k_rows.shape[1]
    k = k_rows.reshape(b, r, kv, hd)
    v = v_rows.reshape(b, r, kv, hd)

    def attend(args):
        q_blk, pos_blk = args
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q_blk, k,
                       preferred_element_type=jnp.float32) * hd ** -0.5
        mask = jnp.arange(r)[None, None, None, None, :] \
            <= pos_blk[:, None, None, :, None]
        p = jax.nn.softmax(jnp.where(mask, s, MASK_VALUE), axis=-1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(v.dtype), v)

    return latent_moe.in_query_blocks(attend, (q, pos), _QUERY_BLOCK)


class GroupedQueryAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, pos, cache=None, cache_rows=None):
        """``x [b, t, width]`` (normed), ``pos [b, t]``. With ``cache``
        (``{"k", "v"}``, ``[rows, R, KV * head_dim]``) the block's lines
        are written in place first, then the lanes' rows are attended where
        they lie; without, the block attends itself."""
        dtype = self.dtype
        b, t, width = x.shape
        kv, hd = self.num_kv_heads, self.head_dim
        mat = lambda name, *shape: self.param(name, param_init(name), shape,
                                              dtype)
        x = x.astype(dtype)
        q = (x @ mat("q", width, self.num_heads * hd)).reshape(
            b, t, kv, self.num_heads // kv, hd)
        k = x @ mat("k", width, kv * hd)
        v = x @ mat("v", width, kv * hd)
        w_o = mat("o", self.num_heads * hd, width)
        new_cache = None
        if cache is None:
            out = _attend_grouped(q, k, v, pos)
        else:
            # in place, first; mode="drop": a position past the row's end
            # must not clamp onto its last cell
            with jax.named_scope("cache.write"):
                rows = jnp.arange(b) if cache_rows is None else cache_rows
                new_cache = {
                    name: cache[name].at[rows[:, None], pos].set(
                        lines.astype(cache[name].dtype), mode="drop")
                    for name, lines in (("k", k), ("v", v))}
            lanes = b
            if t <= _STEP_MAX_BLOCK and cache_rows is not None \
                    and b % _LANE_GROUP == 0:
                lanes = _LANE_GROUP
            part = lambda name, g: gather_rows(
                new_cache[name],
                None if cache_rows is None else cache_rows[g:g + lanes])
            out = jnp.concatenate([
                _attend_grouped(q[g:g + lanes], part("k", g), part("v", g),
                                pos[g:g + lanes])
                for g in range(0, b, lanes)], axis=0)
        out = jnp.dot(out.reshape(b, t, self.num_heads * hd).astype(dtype),
                      w_o, preferred_element_type=jnp.float32)
        return out, new_cache


#: the scope a block's residual sum goes under, by the block's kind
_RESIDUAL_SCOPE = {"M": "ssm.out", "*": "attn.gqa", "E": "moe.shared"}


class HybridLM(nn.Module):
    """The decoder. Sizes are those a chip holds: ``vocab_size`` rows of
    the vocabulary (ids, logits and argmax are over that slice) and
    ``num_experts / expert_share[1]`` experts an ``E`` block;
    ``num_experts`` and ``experts_per_token`` are the router's published
    width and top-k."""
    vocab_size: int
    max_len: int
    pattern: str
    width: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_groups: int
    ssm_state: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    moe_width: int
    shared_width: int
    num_experts: int
    experts_per_token: int
    expert_share: Tuple[int, int] = (0, 1)
    routed_scaling: float = 1.0
    conv_kernel: int = 4
    chunk_size: int = 128
    rms_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16

    #: the cache leaves that are a state a row, with no position axis: what
    #: the serving engine reads to know that no length mask hides them
    cache_state_leaves = ("ssm", "conv")

    @property
    def experts_held(self) -> int:
        return self.num_experts // self.expert_share[1]

    def init_cache(self, batch: int, dtype=None, positions=None):
        """Zeroed cache for ``batch`` rows, one entry a block: ``{"ssm":
        [batch, H, P, N] float32, "conv": [batch, kernel - 1, conv_dim]}``
        for ``M``, ``{"k", "v": [batch, positions, KV * head_dim]}`` for
        ``*`` (``max_len`` positions by default), ``{}`` for ``E``; the
        rows x positions leaves and the convolution's tail in ``dtype``
        (the model's own by default)."""
        dtype = dtype or self.dtype
        positions = self.max_len if positions is None else positions
        conv_dim = self.ssm_heads * self.ssm_head_dim \
            + 2 * self.ssm_groups * self.ssm_state
        line = (batch, positions, self.num_kv_heads * self.head_dim)
        fresh = {
            "M": lambda: {
                "ssm": jnp.zeros((batch, self.ssm_heads, self.ssm_head_dim,
                                  self.ssm_state), jnp.float32),
                "conv": jnp.zeros((batch, self.conv_kernel - 1, conv_dim),
                                  dtype)},
            "*": lambda: {"k": jnp.zeros(line, dtype),
                          "v": jnp.zeros(line, dtype)},
            "E": dict}
        return tuple(fresh[kind]() for kind in self.pattern)

    def cache_bytes_per_row(self, dtype=None) -> int:
        row = jax.eval_shape(lambda: self.init_cache(1, dtype))
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree.leaves(row))

    def prefill_row_len(self, block: int) -> int:
        """Positions of the fresh row a ``block``-token prefill writes and
        attends: the block's own, no more."""
        return block

    @nn.compact
    def __call__(self, input_ids, train: bool = False, cache=None,
                 cache_index=None, page_table=None, cache_rows=None,
                 real_len=None):
        del train                       # no dropout: serving only
        if page_table is not None:
            raise ValueError(
                "HybridLM keeps a recurrent state a row and has no paged "
                "form; serve it from the rectangular KVCachePool")
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

        @jax.named_scope("norm")
        def norm(name, a):
            return rms_norm(
                a, self.param(name, param_init(name), (self.width,),
                              jnp.float32), self.rms_eps)

        new_cache, routed = [], []
        for i, kind in enumerate(self.pattern):
            y = norm(f"mixer_norm_{i}", x)
            layer_cache = None if cache is None else cache[i]
            if kind == "M":
                y, layer_cache = Mamba2Mixer(
                    self.ssm_heads, self.ssm_head_dim, self.ssm_groups,
                    self.ssm_state, self.conv_kernel, self.chunk_size,
                    self.rms_eps, self.dtype, name=f"mixer_{i}")(
                        y, layer_cache, cache_rows, real_len)
            elif kind == "*":
                with jax.named_scope("attn.gqa"):
                    y, layer_cache = GroupedQueryAttention(
                        self.num_heads, self.num_kv_heads, self.head_dim,
                        self.dtype, name=f"mixer_{i}")(
                            y, pos, layer_cache, cache_rows)
            elif kind == "E":
                y, sent = ExpertShare(
                    self.moe_width, self.num_experts, self.experts_per_token,
                    self.expert_share, self.routed_scaling, self.dtype,
                    scoring="sigmoid", activation="relu2",
                    shared_width=self.shared_width, name=f"mixer_{i}")(
                        y.reshape(b * t, self.width))
                with jax.named_scope("moe.route"):
                    routed.append(sent.reshape(b, t, -1))
            else:
                raise ValueError(f"block {i} of pattern {self.pattern!r} is "
                                 f"{kind!r}: not one of 'M', '*', 'E'")
            with jax.named_scope(_RESIDUAL_SCOPE[kind]):
                x = x + y.reshape(b, t, self.width)
            new_cache.append(layer_cache)
        with jax.named_scope("head"):
            if real_len is not None:    # the one row a prefill returns
                x = jnp.take_along_axis(
                    x, (real_len - 1)[:, None, None], axis=1)
            head = self.param("head", param_init("head"),
                              (self.width, self.vocab_size), self.dtype)
            logits = jnp.dot(norm("final_norm", x).astype(self.dtype), head,
                             preferred_element_type=jnp.float32)
        if cache is None:
            return logits
        with jax.named_scope("moe.route"):
            return logits, tuple(new_cache), jnp.stack(routed)


def hybrid_tiny(**kw) -> HybridLM:
    """Test-sized: every kind of block, two state-space blocks around the
    attention one, float32."""
    defaults = dict(
        vocab_size=128, max_len=64, pattern="MEM*EME", width=32,
        ssm_heads=4, ssm_head_dim=8, ssm_groups=2, ssm_state=16,
        num_heads=4, num_kv_heads=2, head_dim=8, moe_width=16,
        shared_width=24, num_experts=8, experts_per_token=3,
        expert_share=(0, 2), routed_scaling=2.5, chunk_size=8,
        dtype=jnp.float32)
    defaults.update(kw)
    return HybridLM(**defaults)
