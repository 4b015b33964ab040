"""Plain reference of the GPT-2 forward pass: float32 ``jax.numpy`` at
``precision=highest``, no cache, no batching tricks, nothing imported from
the program. It follows Radford et al. 2019 (pre-LayerNorm decoder blocks,
learned positions, fused q/k/v projection split in that order, tanh GELU)
and reads the parameter tree under the names models/gpt.py gives it.

Departures from the published model, all stated in the configuration file:
the head is its own float32 matrix with a bias (not the tied embedding),
LayerNorm's epsilon is the configuration's, there is no dropout.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def forward(params, ids, cfg: dict):
    """``ids`` int32 ``[batch, t]`` -> float32 logits ``[batch, t, vocab]``."""
    heads, eps = cfg["n_head"], cfg["layer_norm_epsilon"]
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    b, t = ids.shape
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"]["embedding"][ids] + params["pos_embed"][:t]
        width = x.shape[-1]
        causal = jnp.tril(jnp.ones((t, t), bool))
        for i in range(cfg["n_layer"]):
            p = params[f"layer_{i}"]
            y = _layer_norm(x, p["ln1"], eps)
            q, k, v = jnp.split(_dense(y, p["attn"]["qkv"]), 3, axis=-1)
            q, k, v = (a.reshape(b, t, heads, width // heads)
                       for a in (q, k, v))
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(
                width // heads)
            s = jnp.where(causal, s, -jnp.inf)
            a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
            x = x + _dense(a.reshape(b, t, width), p["attn"]["out"])
            y = _layer_norm(x, p["ln2"], eps)
            y = _dense(_gelu_new(_dense(y, p["mlp"]["fc1"])), p["mlp"]["fc2"])
            x = x + y
        x = _layer_norm(x, params["ln_final"], eps)
        return _dense(x, params["lm_head"])


def token_gaps(params, ids, cfg: dict):
    """For one sequence ``ids[t]``: how far the reference logit of each
    token ``ids[p + 1]`` sits under position ``p``'s largest logit,
    ``[t - 1]`` float32. Computed on the device so that the ``[t, vocab]``
    logits never travel to the host."""
    logits = forward(params, ids[None, :], cfg)[0]
    chosen = jnp.take_along_axis(logits[:-1], ids[1:, None], axis=-1)[:, 0]
    return jnp.max(logits[:-1], axis=-1) - chosen
