"""Builder of the ``gpt2_medium`` family: the program's CausalLM at the sizes
a configuration file states, and seeded inputs for its training cells.

Everything here reads sizes from the configuration (``perf/configs/*.json``
with ``"code": "gpt2_medium"``); nothing is fixed in code, so a size variant
(``gpt2_tiny``) is a data file.
"""

from __future__ import annotations

import numpy as np


def build_model(cfg: dict, mode: str):
    """``mode`` is ``"train"`` or ``"serve"``: serving uses CausalLM's
    defaults (decode mode needs attention="full" and runs un-rematted)."""
    from distkeras_tpu.models.gpt import CausalLM

    extra = dict(cfg.get("train_model", {})) if mode == "train" else {}
    return CausalLM(vocab_size=cfg["vocab_size"], max_len=cfg["n_positions"],
                    num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
                    width=cfg["n_embd"], mlp_dim=cfg["n_inner"], **extra)


def init_params(model, seed: int):
    """The weights, made on the device in one jitted call from the seed, in
    the type they are trained and served in (float32 parameters)."""
    import jax
    import jax.numpy as jnp

    return jax.jit(model.init)(jax.random.key(seed),
                               jnp.zeros((1, 8), jnp.int32))["params"]


def trainer_kwargs(cfg: dict) -> dict:
    kw = dict(cfg["trainer"])
    kw["metrics"] = tuple(kw.get("metrics", ()))
    return kw


def samples_per_chunk(cfg: dict) -> int:
    """Rows one chip takes per device call."""
    t = cfg["trainer"]
    return t["batch_size"] * t["communication_window"] * t["staging_rounds"]


def make_train_data(cfg: dict, n: int, seed: int):
    """``(columns, held)``: ``n`` sequences to train on and one held
    sequence for the check against the reference. Zipf-distributed token
    ids, labels shifted by one."""
    d = cfg["train_data"]
    t = d["sequence_length"]
    rng = np.random.default_rng([seed, 1])
    top = min(cfg["vocab_size"], 50257) - 1          # ids 1..top
    ranks = np.arange(1, top + 1, dtype=np.float64)
    p = ranks ** -d["zipf_exponent"]
    cdf = np.cumsum(p / p.sum())
    ids = 1 + np.searchsorted(cdf, rng.random((n + 1, t + 1)))
    ids = np.minimum(ids, top).astype(np.int32)
    columns = {"features": ids[:n, :t], "label": ids[:n, 1:]}
    held = {"features": ids[n:, :t], "label": ids[n:, 1:]}
    return columns, held


def tokens_per_sample(cfg: dict) -> int:
    return cfg["train_data"]["sequence_length"]


def serving_kwargs(cfg: dict) -> dict:
    s = cfg["serving"]
    return dict(num_slots=s["num_slots"], slot_ladder=tuple(s["slot_ladder"]),
                prefill_buckets=tuple(s["prefill_buckets"]))
