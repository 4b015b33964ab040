"""Builder of the ``latent_moe`` family: the program's LatentMoELM at the
sizes a configuration file states (``perf/configs/*.json`` with ``"code":
"latent_moe"``; the source's keys), as one chip's share of the deployment
the file describes. Nothing is fixed in code, so a size variant
(``mistral_small_4_tiny``) is a data file. Serving only: the family has no
training cell.
"""

from __future__ import annotations


def build_model(cfg: dict, mode: str):
    """``n_routed_experts`` and ``vocab_size`` are what this chip holds;
    the router keeps the published width, ``n_routed_experts *
    expert_share.of``."""
    import jax.numpy as jnp

    from distkeras_tpu.models.latent_moe import LatentMoELM

    if mode != "serve":
        raise SystemExit(f"the latent_moe family has no {mode!r} recipe")
    rope, share = cfg["rope_parameters"], cfg["expert_share"]
    return LatentMoELM(
        vocab_size=cfg["vocab_size"], max_len=cfg["n_positions"],
        num_layers=cfg["num_hidden_layers"], width=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        moe_width=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        num_experts=cfg["n_routed_experts"] * share["of"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_share=(share["index"], share["of"]),
        routed_scaling=cfg["routed_scaling_factor"],
        rms_eps=cfg["rms_norm_eps"], rope_theta=rope["rope_theta"],
        rope_factor=rope["factor"], rope_beta_fast=rope["beta_fast"],
        rope_beta_slow=rope["beta_slow"],
        rope_original_max_len=rope["original_max_position_embeddings"],
        rope_mscale_all_dim=rope["mscale_all_dim"],
        position_beta=rope["llama_4_scaling_beta"],
        dtype=jnp.dtype(cfg["dtype"]))


def init_params(model, seed: int):
    """The weights, made on the device from the seed in the types they are
    served in, one leaf a call: a whole-tree ``model.init`` would hold the
    random bits of many 0.5 GB expert stacks at once beside 10.8 GB of
    parameters. Each leaf is drawn by the model's own initialiser for its
    name (``latent_moe.param_init``), keyed by its path."""
    import functools
    import zlib

    import jax
    import jax.numpy as jnp

    from distkeras_tpu.models.latent_moe import param_init

    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 8), jnp.int32))["params"])
    # one compile an initialiser and shape, not one a leaf
    draw = functools.cache(lambda init: jax.jit(init, static_argnums=(1, 2)))

    def leaf(path, a):
        # the chip's own generator: threefry takes a minute for 5.4 G values
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
