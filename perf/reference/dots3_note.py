"""Plain reference of the ``dots3_note`` decoder (dots3-note-prev): latent
attention of two kinds in one model, full layers under a learned sparse
indexer and window layers at sizes of their own, headwise output gates, a
leading dense layer, sigmoid-routed experts with a selection bias and a
shared expert. Float32 ``jax.numpy`` at ``precision=highest``, expanded
attention only, the selection by a plain stable sort, the window by a mask
over every position, no cache, no ring, nothing imported from the program.
It reads the parameter tree under the names models/latent_moe.py gives it
and the sizes from the configuration file (the source's keys), and it is
given the same share of the experts and of the vocabulary as the program:
what the absent experts would add is left out here too. The unchanged
pieces (RMSNorm, the interleaved rotation, SwiGLU) are
``reference/latent_moe.py``'s.

The layers, as the configuration's source names them and the file's
``assumed`` reads what it leaves open (``x`` is RMSNorm of the residual):

- block ``i``: ``r += Attn_i(RMSNorm(r))``; ``r += MLP(RMSNorm(r))`` for the
  first ``first_k_dense_replace`` blocks (SwiGLU of ``intermediate_size``),
  ``r += MoE(RMSNorm(r))`` after; then RMSNorm and ``logits = r W_head``.
- latent attention at the layer's own sizes (``layer_types[i]``:
  ``full_attention`` the plain keys, ``sliding_attention`` the ``swa_*``
  ones): ``c_q = s_q RMSNorm(x W_qa)``, ``s_q = (hidden / q_lora_rank)^1/2``;
  ``[c | k_r] = x W_kva``, ``c = s_kv RMSNorm(c)``, ``s_kv = (hidden /
  kv_lora_rank)^1/2`` (``apply_mla_qkv_lora_rescale``); ``q_h = [q_h^n |
  RoPE(q_h^r)] = (c_q W_qb)_h``; ``k_sh = [(c_s W_kvb)_h^n | RoPE(k_r,s)]``,
  ``v_sh = (c_s W_kvb)_h^v``; plain rotary frequencies ``theta^(-2j/dim)``
  on interleaved pairs, scale ``(nope + rope)^-1/2``, no position factor;
  ``o_th = sum_{s in S_t} softmax_s(q_th . k_sh scale) v_sh``; the headwise
  gate ``g_t = sigmoid(x_t W_g)`` (one value a head); ``y_t = [g_th o_th]_h
  W_o``.
- a full layer's ``S_t`` is the indexer's: ``q^I_tj = (c_q,t W_Iq)_j``
  (``index_n_heads`` of ``index_head_dim``; ``c_q`` with its rescale),
  ``k^I_s = LayerNorm(x_s W_Ik)`` (scale, bias, eps 1e-6), both rotated on
  their FIRST ``qk_rope_head_dim`` values with the layer's frequencies;
  ``w_t = x_t W_Iw * index_n_heads^-1/2 * index_head_dim^-1/2``; ``I_ts =
  sum_j w_tj relu(q^I_tj . k^I_s)``; ``S_t`` = the ``min(t + 1,
  index_topk)`` positions ``s <= t`` of largest ``I_ts``, ties to the lower
  position (a stable descending sort's first places).
- a window layer's ``S_t = {s : t - sliding_window_size < s <= t}``: the
  window counts the query itself.
- experts: ``g = sigmoid(x W_r)``; the k largest of ``g + b`` (``b`` the
  selection bias; one group); their ``g`` renormalised to sum 1, times
  ``routed_scaling_factor``; ``MoE(x) = E_shared(x) + sum over the top-k
  experts held here of g_e E_e(x)``.

``compute_dtype=bfloat16`` computes the same equations in the nearest
precision below the one the configuration states (index scores and their
sums, router, norms, softmax, gates, logits and residual stream as well);
see ``reference/latent_moe.py``. :func:`selection` hands a test the index
scores and the selected sets of one layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.latent_moe import _wide, rms_norm, rope, swiglu

#: queries attended, and positions given logits, at a time by token_gaps:
#: 128 heads' scores over 11 264 positions are 0.74 GB a block of 128
BLOCK = 128
INDEX_NORM_EPS = 1e-6


def sizes(cfg: dict) -> dict:
    """The configuration's sizes under short names; ``full`` and ``window``
    hold each kind of attention's own."""
    share = cfg.get("expert_share", {"index": 0, "of": 1})
    kind = lambda pre, extra: dict(
        heads=cfg[pre + "num_attention_heads"], q_rank=cfg[pre + "q_lora_rank"],
        rank=cfg[pre + "kv_lora_rank"], nope=cfg[pre + "qk_nope_head_dim"],
        rope=cfg[pre + "qk_rope_head_dim"], v=cfg[pre + "v_head_dim"],
        theta=cfg[pre + "rope_theta"], **extra)
    return dict(
        kinds=cfg["layer_types"], dense=cfg["first_k_dense_replace"],
        eps=cfg["rms_norm_eps"], k=cfg["num_experts_per_tok"],
        index=share["index"], routed_scaling=cfg["routed_scaling_factor"],
        full=kind("", dict(window=0, index_heads=cfg["index_n_heads"],
                           index_dim=cfg["index_head_dim"],
                           index_topk=cfg["index_topk"])),
        window=kind("swa_", dict(window=cfg["sliding_window_size"])))


def inv_freq(a: dict):
    j = jnp.arange(a["rope"] // 2, dtype=jnp.float32)
    return a["theta"] ** (-2.0 * j / a["rope"])


def layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return w * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) + b


def rope_first(x, pos, a: dict):
    """The rotation on the first ``qk_rope_head_dim`` values of the last
    dimension; the rest pass."""
    r = a["rope"]
    return jnp.concatenate(
        [rope(x[..., :r], pos, inv_freq(a)).astype(x.dtype), x[..., r:]],
        axis=-1)


def selected(scores, k: int):
    """``scores [q, s]`` with ``-inf`` where a position may not be chosen
    -> the mask of each row's ``k`` largest, ties to the lower position: a
    stable descending sort, and every position's place in it."""
    order = jnp.argsort(-scores, axis=-1, stable=True)
    place = jnp.argsort(order, axis=-1, stable=True)
    return (place < k) & (scores > -jnp.inf)


def attention(p, x, pos, a: dict, eps, dtype=jnp.float32, block=None,
              keep=False):
    """``x [t, width]`` (normed) -> ``[t, width]`` through one attention of
    sizes ``a`` (``sizes()["full"]`` or ``["window"]``); queries in blocks
    of ``block`` when given. With ``keep`` (unblocked) also what decided
    ``S_t``, ``[t, t]`` (a full layer's index scores; a window layer's
    distances) and the mask of ``S_t`` ``[t, t]``."""
    t, width = x.shape
    h, rank, nope, rp, v = a["heads"], a["rank"], a["nope"], a["rope"], a["v"]
    w = lambda name: _wide(p[name], dtype)
    rescale = lambda r: jnp.asarray((width / r) ** 0.5, dtype)
    c_q = rms_norm(x @ w("q_a"), w("q_norm"), eps) * rescale(a["q_rank"])
    q = (c_q @ w("q_b")).reshape(t, h, nope + rp)
    q_nope = q[..., :nope]
    q_rope = rope(q[..., nope:], pos, inv_freq(a)).astype(dtype)
    kv_a = x @ w("kv_a")
    c = rms_norm(kv_a[:, :rank], w("kv_norm"), eps) * rescale(rank)
    k_rope = rope(kv_a[:, rank:], pos, inv_freq(a)).astype(dtype)
    kv = (c @ w("kv_b")).reshape(t, h, nope + v)
    k_nope, val = kv[..., :nope], kv[..., nope:]
    scale = jnp.asarray((nope + rp) ** -0.5, dtype)
    gate = jax.nn.sigmoid(x @ w("o_gate"))                      # [t, h]
    if not a["window"]:
        ih, idim = a["index_heads"], a["index_dim"]
        iq = rope_first((c_q @ w("index_q")).reshape(t, ih, idim), pos, a)
        ik = rope_first(layer_norm(
            x @ w("index_k"), w("index_k_norm"), w("index_k_bias"),
            INDEX_NORM_EPS), pos, a)
        iw = (x @ w("index_w")) * jnp.asarray(ih ** -0.5 * idim ** -0.5,
                                              dtype)

    def rows(i0, n):
        sl = lambda arr: jax.lax.dynamic_slice_in_dim(arr, i0, n, axis=0)
        causal = pos[None, :] <= sl(pos)[:, None]
        if a["window"]:
            decided = (sl(pos)[:, None] - pos[None, :]).astype(dtype)
            chosen = causal & (pos[None, :] > sl(pos)[:, None] - a["window"])
        else:
            decided = jnp.sum(
                jax.nn.relu(jnp.einsum("qjd,sd->qjs", sl(iq), ik))
                * sl(iw)[:, :, None], axis=1)
            decided = jnp.where(causal, decided, -jnp.inf)
            chosen = selected(decided, a["index_topk"])
        s = jnp.einsum("qhd,khd->hqk", sl(q_nope), k_nope) \
            + jnp.einsum("qhd,kd->hqk", sl(q_rope), k_rope)
        s = jnp.where(chosen[None], s * scale, -jnp.inf)
        out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), val)
        return (out, decided, chosen) if keep else out

    if block is None or t <= block:
        out = rows(0, t)
    else:
        assert t % block == 0 and not keep, (t, block)
        out = jax.lax.map(lambda i0: rows(i0, block),
                          jnp.arange(0, t, block)).reshape(t, h, v)
    if keep:
        out, decided, chosen = out
    y = (out * gate[:, :, None]).reshape(t, h * v) @ w("o")
    return (y, decided, chosen) if keep else y


def routed_part(p, x, z: dict, dtype=jnp.float32):
    """The held experts' part of the mixture: every held expert on every
    token, weighted by its gate where it is among the token's top k and
    by zero elsewhere. Weights are widened an expert at a time."""
    held = p["gate"].shape[0]
    g = jax.nn.sigmoid(x @ _wide(p["router"], dtype))
    _, chosen = jax.lax.top_k(g + _wide(p["router_bias"], dtype), z["k"])
    top = jnp.take_along_axis(g, chosen, axis=-1)
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


def hidden(params, ids, cfg: dict, dtype=jnp.float32, block=None,
           keep_layer=None):
    """``ids [t]`` -> the final norm's output ``[t, width]``; with
    ``keep_layer`` instead that layer's ``(what decided, the mask of
    S_t)``."""
    z = sizes(cfg)
    pos = jnp.arange(ids.shape[0])
    x = _wide(params["tok_embed"][ids], dtype)
    norm = lambda name, a: rms_norm(a, _wide(params[name], dtype), z["eps"])
    for i, kind in enumerate(z["kinds"]):
        a = z["full" if kind == "full_attention" else "window"]
        y = norm(f"attn_norm_{i}", x)
        if i == keep_layer:
            return attention(params[f"attn_{i}"], y, pos, a, z["eps"], dtype,
                             keep=True)[1:]
        x = x + attention(params[f"attn_{i}"], y, pos, a, z["eps"], dtype,
                          block)
        if i < z["dense"]:
            w = lambda name: _wide(params[f"mlp_{i}"][name], dtype)
            x = x + swiglu(norm(f"mlp_norm_{i}", x), w("gate"), w("up"),
                           w("down"))
        else:
            x = x + moe(params[f"moe_{i}"], norm(f"moe_norm_{i}", x), z,
                        dtype)
    return norm("final_norm", x)


def selection(params, ids, cfg: dict, layer: int,
              compute_dtype=jnp.float32):
    """For one sequence ``ids[t]``: what decided layer ``layer``'s ``S_t``,
    ``[t, t]`` (a full layer's index scores, ``-inf`` past the query) and
    the mask of ``S_t`` ``[t, t]``."""
    with jax.default_matmul_precision("highest"):
        return hidden(params, ids, cfg, compute_dtype, keep_layer=layer)


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
