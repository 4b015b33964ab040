"""Plain reference of the latent-attention, routed-expert decoder: float32
``jax.numpy`` at ``precision=highest``, expanded attention only, no cache,
nothing imported from the program. It reads the parameter tree under the
names models/latent_moe.py gives it and the sizes from the configuration
file (the source's keys), and it is given the same share of the experts
and of the vocabulary as the program: what the absent experts would add is
left out here too.

The layer, as the configuration's source and its family define it:

- block: ``x += Attn(RMSNorm(x))``; ``x += MoE(RMSNorm(x))``; after the
  last block RMSNorm, then ``logits = x W_head`` (untied, no bias).
  ``RMSNorm(x) = w * x / sqrt(mean(x^2) + eps)``.
- latent attention (DeepSeek-V2/V3): ``c_q = RMSNorm(x W_qa)``;
  ``[q_nope | q_rope]_i = (c_q W_qb)_i``; ``[c_kv | k_rope] = x W_kva``;
  ``c = RMSNorm(c_kv)``; ``[k_nope | v]_i = (c W_kvb)_i``; score of head i
  ``sigma * s(t) * (q_nope_i(t) . k_nope_i(u) + RoPE_t(q_rope_i) .
  RoPE_u(k_rope))``, causal softmax, output ``concat_i(sum_u p_i v_i) W_o``.
- RoPE on interleaved pairs with YaRN frequencies; ``sigma =
  qk_head_dim^-1/2 * (0.1 ln(factor) + 1)^2``; ``s(t) = 1 + beta *
  ln(1 + floor(t / original_max_position_embeddings))``.
- experts: ``g = softmax(x W_r)`` over all experts, the k largest,
  renormalised to sum 1, times ``routed_scaling_factor``; ``E(x) =
  (silu(x W_gate) * x W_up) W_down``; ``MoE(x) = E_shared(x) + sum over
  the top-k experts held here of g_e E_e(x)``.

``compute_dtype=bfloat16`` computes the same equations in the nearest
precision below the one the configuration states (bfloat16 router, norms,
softmax, logits and residual stream as well). :func:`choices` in that
precision stands in the program's place when a limit's second reading is
taken (``perf/precision_control.py``); nothing times it. The largest gap
alone does not tell the two precisions apart (in either, the worst token
is one whose expert choice flipped at a near-tie); the mean of the gaps,
each counted up to a cap, does (``drivers/serve_closed_bulk.py``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: queries attended, and positions given logits, at a time by token_gaps
BLOCK = 512


def sizes(cfg: dict) -> dict:
    """The configuration's sizes under short names."""
    rp = cfg["rope_parameters"]
    share = cfg.get("expert_share", {"index": 0, "of": 1})
    return dict(
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"],
        eps=cfg["rms_norm_eps"], k=cfg["num_experts_per_tok"],
        index=share["index"],
        routed_scaling=cfg["routed_scaling_factor"],
        theta=rp["rope_theta"], factor=rp["factor"],
        beta_fast=rp["beta_fast"], beta_slow=rp["beta_slow"],
        original=rp["original_max_position_embeddings"],
        mscale_all_dim=rp["mscale_all_dim"],
        beta=rp["llama_4_scaling_beta"])


def rms_norm(x, w, eps):
    return w * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def yarn_inv_freq(z: dict):
    dim = z["rope"]

    def corner(rotations):
        return dim * math.log(z["original"] / (rotations * 2 * math.pi)) \
            / (2 * math.log(z["theta"]))

    low = max(math.floor(corner(z["beta_fast"])), 0)
    high = min(math.ceil(corner(z["beta_slow"])), dim - 1)
    high = high + 0.001 if low == high else high
    j = jnp.arange(dim // 2, dtype=jnp.float32)
    ramp = jnp.clip((j - low) / (high - low), 0.0, 1.0)
    plain = z["theta"] ** (-2.0 * j / dim)
    return plain / z["factor"] * ramp + plain * (1.0 - ramp)


def rope(x, pos, inv_freq):
    """Interleaved pairs: ``(x[2j], x[2j+1])`` turned by ``pos *
    inv_freq[j]``. ``x [t, ..., dim]``, ``pos [t]``."""
    angle = pos.astype(jnp.float32)[:, None] * inv_freq          # [t, dim/2]
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * jnp.cos(angle) - b * jnp.sin(angle),
                     b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1)
    return out.reshape(x.shape)


def softmax_scale(z: dict) -> float:
    mscale = 0.1 * z["mscale_all_dim"] * math.log(z["factor"]) + 1.0 \
        if z["factor"] > 1 else 1.0
    return (z["nope"] + z["rope"]) ** -0.5 * mscale * mscale


def position_scale(pos, z: dict):
    return 1.0 + z["beta"] * jnp.log1p(
        jnp.floor(pos.astype(jnp.float32) / z["original"]))


def _wide(a, dtype):
    return jnp.asarray(a, dtype)


def attention(p, x, pos, z: dict, dtype=jnp.float32, block=None):
    """``x [t, width]`` (normed) -> ``[t, width]``; queries in blocks of
    ``block`` when given."""
    t = x.shape[0]
    h, rank, nope, rp, v = z["heads"], z["rank"], z["nope"], z["rope"], z["v"]
    inv_freq = yarn_inv_freq(z)
    c_q = rms_norm(x @ _wide(p["q_a"], dtype), _wide(p["q_norm"], dtype),
                   z["eps"])
    q = (c_q @ _wide(p["q_b"], dtype)).reshape(t, h, nope + rp)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], pos, inv_freq)
    kv_a = x @ _wide(p["kv_a"], dtype)
    c = rms_norm(kv_a[:, :rank], _wide(p["kv_norm"], dtype), z["eps"])
    k_rope = rope(kv_a[:, rank:], pos, inv_freq)                  # [t, rope]
    kv = (c @ _wide(p["kv_b"], dtype)).reshape(t, h, nope + v)
    k_nope, val = kv[..., :nope], kv[..., nope:]
    scale = (softmax_scale(z) * position_scale(pos, z)).astype(dtype)

    def rows(i0, n):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i0, n, axis=0)
        s = jnp.einsum("qhd,khd->hqk", sl(q_nope), k_nope) \
            + jnp.einsum("qhd,kd->hqk", sl(q_rope).astype(dtype),
                         k_rope.astype(dtype))
        s = s * sl(scale)[None, :, None]
        causal = pos[None, :] <= sl(pos)[:, None]
        s = jnp.where(causal[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), val)

    if block is None or t <= block:
        out = rows(0, t)
    else:
        assert t % block == 0, (t, block)
        out = jax.lax.map(lambda i0: rows(i0, block),
                          jnp.arange(0, t, block)).reshape(t, h, v)
    return out.reshape(t, h * v) @ _wide(p["o"], dtype)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def routed_part(p, x, z: dict, dtype=jnp.float32):
    """The held experts' part of the mixture: every held expert on every
    token, weighted by its gate where it is among the token's top k and
    by zero elsewhere. Weights are widened an expert at a time."""
    held = p["gate"].shape[0]
    g = jax.nn.softmax(x @ _wide(p["router"], dtype), axis=-1)
    top, chosen = jax.lax.top_k(g, z["k"])
    top = top / jnp.sum(top, axis=-1, keepdims=True) * z["routed_scaling"]

    def add(e, acc):
        theirs = jnp.sum(jnp.where(chosen == z["index"] * held + e, top, 0),
                         axis=-1)
        w = lambda name: _wide(jax.lax.dynamic_index_in_dim(
            p[name], e, keepdims=False), dtype)
        return acc + theirs[:, None] * swiglu(x, w("gate"), w("up"),
                                              w("down"))

    return jax.lax.fori_loop(0, held, add, jnp.zeros_like(x))


def moe(p, x, z: dict, dtype=jnp.float32):
    shared = swiglu(x, _wide(p["shared_gate"], dtype),
                    _wide(p["shared_up"], dtype),
                    _wide(p["shared_down"], dtype))
    return shared + routed_part(p, x, z, dtype)


def hidden(params, ids, cfg: dict, dtype=jnp.float32, block=None):
    """``ids [t]`` -> the final norm's output ``[t, width]``."""
    z = sizes(cfg)
    pos = jnp.arange(ids.shape[0])
    x = _wide(params["tok_embed"][ids], dtype)
    for i in range(z["layers"]):
        y = rms_norm(x, _wide(params[f"attn_norm_{i}"], dtype), z["eps"])
        x = x + attention(params[f"attn_{i}"], y, pos, z, dtype, block)
        y = rms_norm(x, _wide(params[f"moe_norm_{i}"], dtype), z["eps"])
        x = x + moe(params[f"moe_{i}"], y, z, dtype)
    return rms_norm(x, _wide(params["final_norm"], dtype), z["eps"])


def forward(params, ids, cfg: dict, compute_dtype=jnp.float32):
    """``ids`` int32 ``[batch, t]`` -> logits ``[batch, t, vocab]`` in
    ``compute_dtype``."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            hidden(params, row, cfg, compute_dtype)
            @ _wide(params["head"], compute_dtype) for row in ids])


def _per_position(params, ids, cfg, dtype, fn, extra):
    """``fn(logits [n, vocab] float32, extra [n])`` at each of the first
    ``t - 1`` positions of one sequence ``ids[t]``, ``[t - 1]``. On the
    device, attention and the ``[t, vocab]`` logits in blocks of
    :data:`BLOCK` positions, so that the context length at the published
    widths fits beside the weights."""
    t = ids.shape[0]
    block = BLOCK if t % BLOCK == 0 else None
    with jax.default_matmul_precision("highest"):
        x = hidden(params, ids, cfg, dtype, block)[:-1]
        head = _wide(params["head"], dtype)
        one = lambda args: fn((args[0] @ head).astype(jnp.float32), args[1])
        if block is None:
            return one((x, extra))
        pad = -(t - 1) % block                # t - 1 rows -> whole blocks
        xp = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, x.shape[-1])
        ep = jnp.pad(extra, (0, pad)).reshape(-1, block)
        return jax.lax.map(one, (xp, ep)).reshape(-1)[:t - 1]


def token_gaps(params, ids, cfg: dict, compute_dtype=jnp.float32,
               chosen=None):
    """For one sequence ``ids[t]``: how far the reference logit of each
    token ``ids[p + 1]`` (or of ``chosen[p]``, what another computation
    chose after the same ``ids[:p + 1]``) sits under position ``p``'s
    largest logit, ``[t - 1]`` float32."""
    def gaps(logits, nxt):
        mine = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1) - mine

    return _per_position(params, ids, cfg, compute_dtype, gaps,
                         ids[1:] if chosen is None else chosen)


def choices(params, ids, cfg: dict, compute_dtype=jnp.float32):
    """The greedy token after each ``ids[:p + 1]``, ``[t - 1]`` int32."""
    return _per_position(
        params, ids, cfg, compute_dtype,
        lambda logits, _: jnp.argmax(logits, axis=-1).astype(jnp.int32),
        ids[1:])
