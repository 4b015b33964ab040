"""Builder of the ``xing4`` family (Xing4.0-29B-A4B): the program's LatentMoELM
with a residual of ``hc_mult`` streams under manifold-constrained
hyper-connections, on latent attention with YaRN, a leading dense layer and
sigmoid-routed experts with a selection bias and a shared expert, at the
sizes a configuration file states (``perf/configs/*.json`` with ``"code":
"xing4"``; the source's keys). Nothing is fixed in code, so a size variant
(``xing4_29b_a4b_tiny``) is a data file. Serving only: the family has no
training cell. The weights' draw and the engine's arguments are the
``latent_moe`` family's: one module and one ``param_init`` for both.
"""

from __future__ import annotations

from builders import latent_moe as family

init_params = family.init_params
serving_kwargs = family.serving_kwargs


def build_model(cfg: dict, mode: str):
    """``n_routed_experts`` and ``vocab_size`` are what this chip holds;
    the router keeps the published width, ``n_routed_experts *
    expert_share.of``. A program whose LatentMoELM has one residual stream
    refuses the configuration here, at once."""
    import jax.numpy as jnp

    from distkeras_tpu.models import latent_moe

    if mode != "serve":
        raise SystemExit(f"the xing4 family has no {mode!r} recipe")
    if not hasattr(latent_moe.LatentMoELM, "hc_mult"):
        raise SystemExit("this program's LatentMoELM has one residual "
                         "stream: it cannot build the xing4 family")
    rs, share = cfg["rope_scaling"], cfg["expert_share"]
    if rs["type"] != "yarn" or rs["mscale"] != rs["mscale_all_dim"] \
            or cfg["scoring_func"] != "sigmoid" \
            or cfg["topk_method"] != "noaux_tc" or cfg["n_group"] != 1:
        raise SystemExit("the xing4 builder knows YaRN with mscale = "
                         "mscale_all_dim and one group of sigmoid scores "
                         "with a selection bias")
    return latent_moe.LatentMoELM(
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
        rms_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        rope_factor=rs["factor"], rope_beta_fast=rs["beta_fast"],
        rope_beta_slow=rs["beta_slow"],
        rope_original_max_len=rs["original_max_position_embeddings"],
        rope_mscale_all_dim=rs["mscale_all_dim"],
        dtype=jnp.dtype(cfg["dtype"]),
        dense_layers=cfg["first_k_dense_replace"],
        dense_width=cfg["intermediate_size"], scoring=cfg["scoring_func"],
        hc_mult=cfg["hc_mult"], hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"],
        hc_eps=cfg["hc_eps"],
        hc_clamp=(float(cfg["mhc_h_res_clamp_min"]),
                  float(cfg["mhc_h_res_clamp_max"])))
