"""Plain reference of the ``nemotron_h`` family's decoder: float32
``jax.numpy`` at ``precision=highest``, the state-space recurrence as a
plain ``lax.scan`` over positions (no chunks), no cache, no batching, nothing
imported from the program. It reads the parameter tree under the names
models/hybrid.py gives it and the sizes from the configuration file (the
source's keys), and it is given the same share of the experts and of the
vocabulary as the program: what the absent experts would add is left out
here too.

The model, as its source (the ``nemotron_h`` model of ``transformers``; the
Mamba-2 layer of arXiv:2405.21060) defines it. Block ``l`` is one mixer,
by its letter in ``hybrid_override_pattern``: ``x += Mixer_l(RMSNorm(x))``;
after the last block RMSNorm, then ``logits = x W_head`` (untied, no
bias); plain embedding lookup. ``RMSNorm(x) = w * x / sqrt(mean(x^2) +
eps)``.

- ``M``, Mamba-2 (``H`` heads of ``P``, ``G`` groups, state ``N``, kernel
  ``K``): ``[z | xBC | dt] = u W_in``; ``xBC = silu(conv(xBC) + b)``,
  ``conv(x)_t = sum_k w_k x_{t - K + 1 + k}`` causal and depthwise; ``xBC
  -> x [H, P], B [G, N], C [G, N]``, head ``h`` reads group ``h // (H /
  G)``; ``Delta_t = softplus(dt_t + dt_bias)``; ``A = -exp(A_log)``; ``h_t
  = exp(Delta_t A) h_{t-1} + Delta_t x_t (x) B_t``; ``y_t = h_t C_t + D
  x_t``; ``y = RMSNorm over groups of H P / G of (y * silu(z)) * w``; out
  ``y W_out``.
- ``*``, attention: ``q, k, v, o`` without bias, ``num_attention_heads``
  query heads on ``num_key_value_heads`` key/value heads, scores ``q . k /
  sqrt(head_dim)``, causal softmax, NO rotary and no learned position.
- ``E``, experts: ``s = sigmoid(x W_r)``; the k largest of ``s + b``;
  weights ``s`` at the chosen over their sum, times
  ``routed_scaling_factor``; ``E_i(x) = relu(x W_up,i)^2 W_down,i``;
  ``MoE(x) = E_shared(x) + sum over the chosen experts held here``.

Departures from the published description: none in the equations. The
config's ``rope_theta`` and ``partial_rotary_factor`` go unused, as in the
``transformers`` model (its attention applies no position); ``n_group =
topk_group = 1``, so the router's grouping is the identity and is left out;
``time_step_min/max/floor`` shape initial weights only. Logits and
attention are taken in blocks of :data:`BLOCK` positions so that the
context length at the published widths fits on the chip beside the weights;
the recurrence is not blocked.

``compute_dtype=bfloat16`` computes the same equations in the nearest
precision below the one the configuration states (bfloat16 router, norms,
softmax, ``Delta``, decays, state, residual stream and logits as well);
:func:`choices` in that precision stands in the program's place when a
limit's second reading is taken (``perf/precision_control.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: queries attended, and positions given logits, at a time by token_gaps
BLOCK = 512


def sizes(cfg: dict) -> dict:
    """The configuration's sizes under short names."""
    share = cfg.get("expert_share", {"index": 0, "of": 1})
    return dict(
        pattern=cfg["hybrid_override_pattern"], eps=cfg["norm_eps"],
        H=cfg["mamba_num_heads"], P=cfg["mamba_head_dim"],
        G=cfg["n_groups"], N=cfg["ssm_state_size"], K=cfg["conv_kernel"],
        heads=cfg["num_attention_heads"], kv=cfg["num_key_value_heads"],
        hd=cfg["head_dim"], k=cfg["num_experts_per_tok"],
        index=share["index"], routed_scaling=cfg["routed_scaling_factor"])


def rms_norm(x, w, eps):
    return w * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _wide(a, dtype):
    return jnp.asarray(a, dtype)


def mamba(p, u, z: dict, dtype=jnp.float32):
    """``u [t, width]`` (normed) -> ``[t, width]``: the recurrence one
    position at a time from a zero state."""
    t = u.shape[0]
    H, P, G, N, K = z["H"], z["P"], z["G"], z["N"], z["K"]
    inner = H * P
    w = lambda name: _wide(p[name], dtype)
    proj = u @ w("in_proj")
    gate, xbc, dt = (proj[:, :inner], proj[:, inner:-H], proj[:, -H:])
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), dtype), xbc])
    xbc = jax.nn.silu(w("conv_b") + sum(
        w("conv_w")[k] * padded[k:k + t] for k in range(K)))
    x = xbc[:, :inner].reshape(t, G, H // G, P)
    b_mat = xbc[:, inner:inner + G * N].reshape(t, G, N)
    c_mat = xbc[:, inner + G * N:].reshape(t, G, N)
    dt = jax.nn.softplus(dt + w("dt_bias")).reshape(t, G, H // G)
    a = -jnp.exp(w("A_log")).reshape(G, H // G)
    d_skip = w("D").reshape(G, H // G)

    def step(h, at):
        x_t, b_t, c_t, dt_t = at
        h = jnp.exp(dt_t * a)[..., None, None] * h \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        y = jnp.sum(h * c_t[:, None, None, :], axis=-1) \
            + d_skip[..., None] * x_t
        return h, y

    _, y = jax.lax.scan(step, jnp.zeros((G, H // G, P, N), dtype),
                        (x, b_mat, c_mat, dt))
    y = y.reshape(t, G, inner // G) * jax.nn.silu(gate).reshape(
        t, G, inner // G)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + z["eps"])
    return (y.reshape(t, inner) * w("gate_norm")) @ w("out_proj")


def attention(p, x, z: dict, dtype=jnp.float32, block=None):
    """``x [t, width]`` (normed) -> ``[t, width]``; queries in blocks of
    ``block`` when given. No positional term."""
    t = x.shape[0]
    heads, kv, hd = z["heads"], z["kv"], z["hd"]
    q = (x @ _wide(p["q"], dtype)).reshape(t, kv, heads // kv, hd)
    k = (x @ _wide(p["k"], dtype)).reshape(t, kv, hd)
    v = (x @ _wide(p["v"], dtype)).reshape(t, kv, hd)
    pos = jnp.arange(t)

    def rows(i0, n):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i0, n, axis=0)
        s = jnp.einsum("qgrd,kgd->grqk", sl(q), k) * jnp.asarray(
            hd ** -0.5, dtype)
        causal = pos[None, :] <= sl(pos)[:, None]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(s, axis=-1), v)

    if block is None or t <= block:
        out = rows(0, t)
    else:
        assert t % block == 0, (t, block)
        out = jax.lax.map(lambda i0: rows(i0, block),
                          jnp.arange(0, t, block)).reshape(t, kv, -1, hd)
    return out.reshape(t, heads * hd) @ _wide(p["o"], dtype)


def relu2_expert(x, up, down):
    return jnp.square(jax.nn.relu(x @ up)) @ down


def routed_part(p, x, z: dict, dtype=jnp.float32):
    """The held experts' part of the mixture: every held expert on every
    token, weighted by its share where it is among the token's k chosen
    and by zero elsewhere. Weights are widened an expert at a time."""
    held = p["up"].shape[0]
    s = jax.nn.sigmoid(x @ _wide(p["router"], dtype))
    _, chosen = jax.lax.top_k(s + _wide(p["router_bias"], dtype), z["k"])
    top = jnp.take_along_axis(s, chosen, axis=-1)
    top = top / jnp.sum(top, axis=-1, keepdims=True) * jnp.asarray(
        z["routed_scaling"], dtype)

    def add(e, acc):
        theirs = jnp.sum(jnp.where(chosen == z["index"] * held + e, top, 0),
                         axis=-1)
        w = lambda name: _wide(jax.lax.dynamic_index_in_dim(
            p[name], e, keepdims=False), dtype)
        return acc + theirs[:, None] * relu2_expert(x, w("up"), w("down"))

    return jax.lax.fori_loop(0, held, add, jnp.zeros_like(x))


def moe(p, x, z: dict, dtype=jnp.float32):
    return relu2_expert(x, _wide(p["shared_up"], dtype),
                        _wide(p["shared_down"], dtype)) \
        + routed_part(p, x, z, dtype)


def hidden(params, ids, cfg: dict, dtype=jnp.float32, block=None):
    """``ids [t]`` -> the final norm's output ``[t, width]``."""
    z = sizes(cfg)
    x = _wide(params["tok_embed"][ids], dtype)
    for i, kind in enumerate(z["pattern"]):
        p = params[f"mixer_{i}"]
        y = rms_norm(x, _wide(params[f"mixer_norm_{i}"], dtype), z["eps"])
        if kind == "M":
            x = x + mamba(p, y, z, dtype)
        elif kind == "*":
            x = x + attention(p, y, z, dtype, block)
        else:
            assert kind == "E", kind
            x = x + moe(p, y, z, dtype)
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
