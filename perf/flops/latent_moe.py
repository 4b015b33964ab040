"""Bytes the latent-attention, routed-expert decoder *needs* in a decode
step, from the configuration's shapes alone (the source's keys; the experts
and the vocabulary are the chip's share, as the file states them).

A decode step is bound by bytes. What it cannot avoid reading: every
matrix outside the routed experts once, in bfloat16 (norm vectors and the
router in float32); the chip's slice of the head; the cached line of every
position its lanes really hold (the latent and the one rotary key, bfloat16:
not the padding a stored line carries); and the weights of the held experts
that receive a token. With the configuration's lanes each sending
``num_experts_per_tok`` assignments evenly over all the published experts,
a held expert is idle with probability ``(1 - k / E)^lanes``, so the share
expected to be active is ``1 - (1 - k / E)^lanes`` (98.3 % at 128 lanes, 4
of 128): never more than an implementation must read. The embedding rows
of the step's tokens are negligible and left out.
"""

from __future__ import annotations


def layer_dense_bytes(cfg: dict) -> float:
    """One layer outside its routed experts: attention, shared expert,
    router, norms."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    q_rank, kv_rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    attention = (d * q_rank + q_rank * heads * (nope + rope)
                 + d * (kv_rank + rope) + kv_rank * heads * (nope + v)
                 + heads * v * d)
    shared = 3 * d * cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    published = cfg["n_routed_experts"] * cfg["expert_share"]["of"]
    vectors = q_rank + kv_rank + 2 * d + d * published   # norms, router
    return 2.0 * (attention + shared) + 4.0 * vectors


def expert_bytes(cfg: dict) -> float:
    """One routed expert's three matrices, bfloat16."""
    return 2.0 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expected_active_share(cfg: dict, lanes: int) -> float:
    """Share of the held experts that receive at least one of ``lanes``
    tokens under even routing over the published experts."""
    published = cfg["n_routed_experts"] * cfg["expert_share"]["of"]
    return 1.0 - (1.0 - cfg["num_experts_per_tok"] / published) ** lanes


def decode_weight_bytes(cfg: dict) -> float:
    """Weights one decode step has to read at the configuration's lanes:
    everything outside the routed experts once, the head's slice, the
    final norm, and the held experts expected to be active."""
    lanes = cfg["serving"]["num_slots"]
    experts = cfg["n_routed_experts"] * expert_bytes(cfg) \
        * expected_active_share(cfg, lanes)
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] \
        + 4.0 * cfg["hidden_size"]
    return cfg["num_hidden_layers"] * (layer_dense_bytes(cfg) + experts) \
        + head


def cache_bytes_per_position(cfg: dict) -> float:
    """Cached bytes one context position holds over all layers: the
    latent and the one rotary key, bfloat16."""
    return 2.0 * cfg["num_hidden_layers"] \
        * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def decode_step_bytes(cfg: dict, context_positions: float) -> float:
    """Bytes one decode step needs to move: the weights above, plus the
    cached lines of the ``context_positions`` positions its lanes really
    hold (summed over lanes). Not what the rectangular pool moves."""
    return decode_weight_bytes(cfg) \
        + context_positions * cache_bytes_per_position(cfg)
