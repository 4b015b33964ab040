"""Builder of the ``nemotron_h`` family: the program's HybridLM (Mamba-2
blocks, grouped-query attention without positions, sigmoid-routed relu^2
experts, one mixer a block by ``hybrid_override_pattern``) at the sizes a
configuration file states (``perf/configs/*.json`` with ``"code":
"nemotron_h"``; the source's keys), as one chip's share of the deployment
the file describes. Nothing is fixed in code, so a size variant
(``nemotron3_nano_tiny``) is a data file. Serving only: the family has no
training cell.
"""

from __future__ import annotations


def build_model(cfg: dict, mode: str):
    """``n_routed_experts`` and ``vocab_size`` are what this chip holds;
    the router keeps the published width, ``n_routed_experts *
    expert_share.of``."""
    import jax.numpy as jnp

    from distkeras_tpu.models.hybrid import HybridLM

    if mode != "serve":
        raise SystemExit(f"the nemotron_h family has no {mode!r} recipe")
    share = cfg["expert_share"]
    if len(cfg["hybrid_override_pattern"]) != cfg["num_hidden_layers"]:
        raise SystemExit("hybrid_override_pattern and num_hidden_layers "
                         "disagree")
    return HybridLM(
        vocab_size=cfg["vocab_size"], max_len=cfg["n_positions"],
        pattern=cfg["hybrid_override_pattern"], width=cfg["hidden_size"],
        ssm_heads=cfg["mamba_num_heads"], ssm_head_dim=cfg["mamba_head_dim"],
        ssm_groups=cfg["n_groups"], ssm_state=cfg["ssm_state_size"],
        conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        moe_width=cfg["moe_intermediate_size"],
        shared_width=cfg["moe_shared_expert_intermediate_size"]
        * cfg["n_shared_experts"],
        num_experts=cfg["n_routed_experts"] * share["of"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_share=(share["index"], share["of"]),
        routed_scaling=cfg["routed_scaling_factor"],
        rms_eps=cfg["norm_eps"], dtype=jnp.dtype(cfg["dtype"]))


def init_params(model, seed: int):
    """The weights, made on the device from the seed in the types they are
    served in, one leaf a call (a whole-tree ``model.init`` would hold the
    random bits of many 0.6 GB expert stacks at once beside 10.6 GB of
    parameters). Each leaf is drawn by the model's own initialiser for its
    name (``hybrid.param_init``), keyed by its path."""
    import functools
    import zlib

    import jax
    import jax.numpy as jnp

    from distkeras_tpu.models.hybrid import param_init

    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 8), jnp.int32))["params"])
    # one compile an initialiser and shape, not one a leaf
    draw = functools.cache(lambda init: jax.jit(init, static_argnums=(1, 2)))

    def leaf(path, a):
        # the chip's own generator: threefry takes a minute for 5.3 G values
        key = jax.random.fold_in(
            jax.random.key(seed, impl="rbg"),
            zlib.crc32("/".join(p.key for p in path).encode()))
        return draw(param_init(path[-1].key))(key, a.shape, a.dtype)

    return jax.tree_util.tree_map_with_path(leaf, shapes)


def serving_kwargs(cfg: dict) -> dict:
    s = cfg["serving"]
    return dict(num_slots=s["num_slots"], slot_ladder=tuple(s["slot_ladder"]),
                prefill_buckets=tuple(s["prefill_buckets"]),
                queue_capacity=s["queue_capacity"])
