"""Plain reference of the ``xing4`` decoder (Xing4.0-29B-A4B): latent
attention with YaRN, a leading dense layer, sigmoid-routed experts with a
selection bias and a shared expert, and a residual of ``hc_mult`` streams
under manifold-constrained hyper-connections. Float32 ``jax.numpy`` at
``precision=highest``, expanded attention only, no cache, nothing imported
from the program. It reads the parameter tree under the names
models/latent_moe.py gives it and the sizes from the configuration file (the
source's keys). The unchanged pieces (RMSNorm, the interleaved rotation, the
latent attention, SwiGLU) are ``reference/latent_moe.py``'s.

The model, as the configuration's source and the papers it names define it,
and the file's ``assumed`` reads what they leave open (``x`` is the residual,
``n`` streams of ``hidden_size``, ``[t, n, C]``):

- entry: ``x_i = embed[ids]`` for every stream ``i`` (HC).
- around each sub-layer ``F`` (block ``i``'s attention; then its SwiGLU of
  ``intermediate_size`` for the first ``first_k_dense_replace`` blocks, its
  expert layer after), with parameters of its own (mHC, arXiv:2512.24880,
  equations 7 to 9):
  ``v = vec(x) / sqrt(mean(vec(x)^2) + rms_norm_eps)`` over ``n C`` values;
  ``H_pre = sigmoid(alpha_pre v phi_pre + b_pre)``;
  ``H_post = 2 sigmoid(alpha_post v phi_post + b_post)``;
  ``H_res = SK(exp(clip(alpha_res mat(v phi_res) + b_res, min, max)))``,
  ``mat`` row-major, ``SK`` ``hc_sinkhorn_iters`` times ``M <- T_r(T_c(M))``
  (every column, then every row, divided by its sum plus ``hc_eps``);
  ``h = sum_i H_pre[i] x_i``; ``y = F(RMSNorm_w(h))``;
  ``x_i <- sum_j H_res[i, j] x_j + H_post[i] y``.
- exit: ``logits = RMSNorm_w(sum_i x_i) W_head`` (HC's final sum).
- latent attention (DeepSeek-V3): ``reference/latent_moe.attention`` with
  YaRN frequencies on interleaved pairs, ``sigma = qk_head_dim^-1/2 * (0.1
  ln(factor) + 1)^2`` and no position factor.
- experts: ``g = sigmoid(x W_r)``; the k largest of ``g + b`` (``b`` the
  selection bias, ``noaux_tc``; one group); their ``g`` renormalised to sum
  1, times ``routed_scaling_factor``; ``MoE(x) = E_shared(x) + sum over the
  top-k experts held here of g_e E_e(x)``. Each expert is computed on the
  tokens routed to it and no others, :data:`EXPERT_BLOCK` at a time, so
  that a check of 64 requests over 5376 positions with 64 experts a layer
  costs what the routing needs and not 16 times that.

``compute_dtype=bfloat16`` computes the same equations in the nearest
precision below the one the configuration states (router, norms, softmax,
the streams, the whole mapping and the logits as well); see
``reference/latent_moe.py`` and ``perf/precision_control.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.latent_moe import _wide, attention, rms_norm, swiglu

#: queries attended, and positions given logits, at a time by token_gaps (a
#: divisor of the configuration's 5376 positions)
BLOCK = 256
#: tokens an expert takes at a time
EXPERT_BLOCK = 256


def sizes(cfg: dict) -> dict:
    """The configuration's sizes under short names, those of
    ``reference/latent_moe.sizes`` among them."""
    rs = cfg["rope_scaling"]
    share = cfg.get("expert_share", {"index": 0, "of": 1})
    return dict(
        layers=cfg["num_hidden_layers"], dense=cfg["first_k_dense_replace"],
        heads=cfg["num_attention_heads"], rank=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        v=cfg["v_head_dim"], eps=cfg["rms_norm_eps"],
        k=cfg["num_experts_per_tok"], index=share["index"],
        routed_scaling=cfg["routed_scaling_factor"],
        theta=cfg["rope_theta"], factor=rs["factor"],
        beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
        original=rs["original_max_position_embeddings"],
        mscale_all_dim=rs["mscale_all_dim"], beta=0.0,
        streams=cfg["hc_mult"], iters=cfg["hc_sinkhorn_iters"],
        hc_eps=cfg["hc_eps"],
        clamp=(cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]))


def mapping(p, x, z: dict, dtype=jnp.float32):
    """``(H_pre [t, n], H_post [t, n], H_res [t, n, n])`` of one sub-layer
    for the streams ``x [t, n, C]``."""
    t, n, c = x.shape
    w = lambda name: _wide(p[name], dtype)
    v = x.reshape(t, n * c)
    v = v / jnp.sqrt(jnp.mean(v * v, axis=-1, keepdims=True) + z["eps"])
    pre = jax.nn.sigmoid(w("alpha_pre") * (v @ w("phi_pre")) + w("b_pre"))
    post = 2 * jax.nn.sigmoid(w("alpha_post") * (v @ w("phi_post"))
                              + w("b_post"))
    res = w("alpha_res") * (v @ w("phi_res")).reshape(t, n, n) + w("b_res")
    m = jnp.exp(jnp.clip(res, *z["clamp"]))
    for _ in range(z["iters"]):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + z["hc_eps"])  # columns
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + z["hc_eps"])  # rows
    return pre, post, m


def around(p, x, f, z: dict, dtype=jnp.float32):
    """The streams ``x [t, n, C]`` after sub-layer ``f`` (``h [t, C]`` ->
    ``[t, C]``) under its mapping ``p``."""
    pre, post, res = mapping(p, x, z, dtype)
    y = f(jnp.einsum("tn,tnc->tc", pre, x))
    return jnp.einsum("tij,tjc->tic", res, x) + post[:, :, None] * y[:, None]


def routed_part(p, x, z: dict, dtype=jnp.float32):
    """The held experts' part of the mixture, an expert at a time over the
    tokens routed to it (ascending), :data:`EXPERT_BLOCK` of them at a
    time; its weights widened once an expert."""
    held, n = p["gate"].shape[0], x.shape[0]
    blk = min(EXPERT_BLOCK, n)
    g = jax.nn.sigmoid(x @ _wide(p["router"], dtype))
    _, chosen = jax.lax.top_k(g + _wide(p["router_bias"], dtype), z["k"])
    top = jnp.take_along_axis(g, chosen, axis=-1)
    top = top / jnp.sum(top, axis=-1, keepdims=True) * z["routed_scaling"]

    def expert(e, acc):
        mine = chosen == z["index"] * held + e                  # [n, k]
        weight = jnp.sum(jnp.where(mine, top, 0), axis=-1)      # [n]
        sent = jnp.any(mine, axis=-1)
        count = jnp.sum(sent)
        rows = jnp.nonzero(sent, size=n + blk, fill_value=0)[0]
        w = lambda name: _wide(jax.lax.dynamic_index_in_dim(
            p[name], e, keepdims=False), dtype)
        gate, up, down = w("gate"), w("up"), w("down")

        def block(j, acc):
            at = jax.lax.dynamic_slice_in_dim(rows, j * blk, blk)
            real = j * blk + jnp.arange(blk) < count
            y = swiglu(x[at], gate, up, down)
            return acc.at[at].add(
                y * jnp.where(real, weight[at], 0)[:, None])

        return jax.lax.fori_loop(0, (count + blk - 1) // blk, block, acc)

    return jax.lax.fori_loop(0, held, expert, jnp.zeros_like(x))


def moe(p, x, z: dict, dtype=jnp.float32):
    shared = swiglu(x, _wide(p["shared_gate"], dtype),
                    _wide(p["shared_up"], dtype),
                    _wide(p["shared_down"], dtype))
    return shared + routed_part(p, x, z, dtype)


def hidden(params, ids, cfg: dict, dtype=jnp.float32, block=None):
    """``ids [t]`` -> the final norm's output ``[t, width]``."""
    z = sizes(cfg)
    t = ids.shape[0]
    pos = jnp.arange(t)
    e = _wide(params["tok_embed"][ids], dtype)
    x = jnp.broadcast_to(e[:, None], (t, z["streams"], e.shape[-1]))
    norm = lambda name, a: rms_norm(a, _wide(params[name], dtype), z["eps"])
    for i in range(z["layers"]):
        x = around(params[f"hc_attn_{i}"], x, lambda h: attention(
            params[f"attn_{i}"], norm(f"attn_norm_{i}", h), pos, z, dtype,
            block), z, dtype)
        if i < z["dense"]:
            w = lambda name: _wide(params[f"mlp_{i}"][name], dtype)
            f = lambda h: swiglu(norm(f"mlp_norm_{i}", h), w("gate"),
                                 w("up"), w("down"))
        else:
            f = lambda h: moe(params[f"moe_{i}"], norm(f"moe_norm_{i}", h),
                              z, dtype)
        x = around(params[f"hc_mlp_{i}"], x, f, z, dtype)
    return norm("final_norm", jnp.sum(x, axis=1))


def forward(params, ids, cfg: dict, compute_dtype=jnp.float32):
    """``ids`` int32 ``[batch, t]`` -> logits ``[batch, t, vocab]`` in
    ``compute_dtype``."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            hidden(params, row, cfg, compute_dtype)
            @ _wide(params["head"], compute_dtype) for row in ids])


def _per_position(params, ids, cfg, dtype, fn, extra):
    """``fn(logits [n, vocab] float32, extra [n])`` at each of the first
    ``t - 1`` positions of one sequence ``ids[t]``, ``[t - 1]``; attention
    and the ``[t, vocab]`` logits in blocks of :data:`BLOCK` positions where
    they divide ``t``."""
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
