"""Builder of the ``dots3_note`` family: the program's LatentMoELM over
layers of two kinds (full latent attention under the indexer, window latent
attention at its own ``swa_*`` sizes in a ring), headwise gates and the rank
rescale on, a leading dense layer, sigmoid-scored routed experts with a
selection bias and a shared expert, at the sizes a configuration file states
(``perf/configs/*.json`` with ``"code": "dots3_note"``; the source's keys),
as one chip's share of the deployment the file describes. Nothing is fixed
in code, so a size variant (``dots3_note_tiny``) is a data file. Serving
only: the family has no training cell. The engine's arguments and the
weights' draw are the ``latent_moe`` family's, one module and one
``param_init`` for both, but for the matrices that read a rescaled latent
(:func:`init_params`).
"""

from __future__ import annotations

from builders import latent_moe as family

KINDS = {"full_attention": "F", "sliding_attention": "S"}


def build_model(cfg: dict, mode: str):
    """``n_routed_experts`` and ``vocab_size`` are what this chip holds;
    the router keeps the published width, ``n_routed_experts *
    expert_share.of``. Plain rotary frequencies are the YaRN path at factor
    1. A program without layer kinds (before PR 35) has no ``WindowSizes``
    and refuses the configuration here, at once."""
    import jax.numpy as jnp

    from distkeras_tpu.models import latent_moe

    if mode != "serve":
        raise SystemExit(f"the dots3_note family has no {mode!r} recipe")
    if not hasattr(latent_moe, "WindowSizes"):
        raise SystemExit("this program's LatentMoELM has one kind of layer: "
                         "it cannot build the dots3_note family")
    if cfg["rope_scaling"] is not None or cfg["scoring_func"] != "sigmoid" \
            or cfg["attention_gate_type"] != "headwise" \
            or cfg["swa_attention_gate_type"] != "headwise":
        raise SystemExit("the dots3_note builder knows plain rotary "
                         "frequencies, sigmoid scores and headwise gates")
    share, serving = cfg["expert_share"], cfg["serving"]
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
        dtype=jnp.dtype(cfg["dtype"]),
        dense_layers=cfg["first_k_dense_replace"],
        dense_width=cfg["intermediate_size"], scoring=cfg["scoring_func"],
        index_heads=cfg["index_n_heads"], index_dim=cfg["index_head_dim"],
        index_topk=cfg["index_topk"],
        layer_kinds=tuple(KINDS[kind] for kind in cfg["layer_types"]),
        window_sizes=latent_moe.WindowSizes(
            window=cfg["sliding_window_size"], ring=serving["ring_cells"],
            num_heads=cfg["swa_num_attention_heads"],
            q_lora_rank=cfg["swa_q_lora_rank"],
            kv_lora_rank=cfg["swa_kv_lora_rank"],
            qk_nope_head_dim=cfg["swa_qk_nope_head_dim"],
            qk_rope_head_dim=cfg["swa_qk_rope_head_dim"],
            v_head_dim=cfg["swa_v_head_dim"],
            rope_theta=cfg["swa_rope_theta"]),
        head_gate=True, rank_rescale=cfg["apply_mla_qkv_lora_rescale"])


def serving_kwargs(cfg: dict) -> dict:
    """The family's, and ``admit_spacing``: the decode steps the engine
    keeps between two admissions while lanes decode, in shares of the
    admitted request (none by default)."""
    return dict(family.serving_kwargs(cfg),
                admit_spacing=cfg["serving"].get("admit_spacing", 0.0))


def init_params(model, seed: int):
    """The ``latent_moe`` family's draw (every matrix normal with variance 1
    / fan-in), with the matrices that read a rescaled latent drawn at the
    variance the rescale is there for: ``q_b`` and ``index_q`` read ``c_q``
    times ``(width / q_lora_rank)^1/2`` and ``kv_b`` reads ``c`` times
    ``(width / kv_lora_rank)^1/2``, so their variance is 1 / width, the
    fan-in of the uncompressed projection (LongCat-Flash states the
    rescale's purpose so: queries and keys of unit variance at
    initialisation whatever the rank). At 1 / rank the attention logits of
    random weights have a deviation of ~6 where a trained model's and the
    sibling configurations' have ~1, the softmax falls on a few positions,
    and a rounding in one layer's output moves the next layer's selection
    (my chip run, PR 35: 20 % of the emitted tokens the float32 reference's
    argmax, in the stated precision and in the one below alike)."""
    import jax

    params = family.init_params(model, seed)
    if not model.rank_rescale:
        return params
    # from variance 1 / rank (the matrix's rows) to 1 / width, in place
    shrink = jax.jit(lambda a: (a.astype("float32") * (
        a.shape[0] / model.width) ** 0.5).astype(a.dtype), donate_argnums=0)
    return jax.tree_util.tree_map_with_path(
        lambda path, a: shrink(a)
        if path[-1].key in ("q_b", "index_q", "kv_b") else a, params)
