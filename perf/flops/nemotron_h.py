"""Bytes and operations the ``nemotron_h`` family's decoder *needs*, from
the configuration's shapes alone (the source's keys; the experts and the
vocabulary are the chip's share, as the file states them).

A decode step is bound by bytes. What it cannot avoid moving: every matrix
outside the routed experts once, in bfloat16 (the vectors and the router in
float32); the chip's slice of the head; the weights of the held experts
that receive a token (a held expert is idle with probability ``(1 - k /
E)^lanes`` under even routing over the published experts); the cached keys
and values of every position its lanes really hold, in the attention
blocks; and, in every state-space block, each lane's recurrent state and
convolution tail read once and written once: the state has no position
axis, so its cost is the same at any context length. The embedding rows
of the step's tokens are negligible and left out.

A prefill is bound by operations: two a parameter a real token in every
matrix it multiplies (a token meets the held experts it is sent to: ``k *
held / published`` of them on average), the scan's own products, and the
attention's scores and sums over the causal half. Padding is not counted:
what the bucket adds is the implementation's, not the prompt's.
"""

from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner = heads * p
    pattern = cfg["hybrid_override_pattern"]
    return dict(
        d=cfg["hidden_size"], heads=heads, p=p, groups=groups, n=n,
        inner=inner, conv_dim=inner + 2 * groups * n,
        taps=cfg["conv_kernel"], chunk=cfg["chunk_size"],
        q_width=cfg["num_attention_heads"] * cfg["head_dim"],
        kv_width=cfg["num_key_value_heads"] * cfg["head_dim"],
        expert=cfg["moe_intermediate_size"],
        shared=cfg["moe_shared_expert_intermediate_size"]
        * cfg["n_shared_experts"],
        held=cfg["n_routed_experts"],
        published=cfg["n_routed_experts"] * cfg["expert_share"]["of"],
        k=cfg["num_experts_per_tok"],
        m_blocks=pattern.count("M"), a_blocks=pattern.count("*"),
        e_blocks=pattern.count("E"))


def mamba_matrix_params(cfg: dict) -> int:
    """The two projections of one state-space block."""
    z = _sizes(cfg)
    return z["d"] * (z["inner"] + z["conv_dim"] + z["heads"]) \
        + z["inner"] * z["d"]


def attention_matrix_params(cfg: dict) -> int:
    z = _sizes(cfg)
    return 2 * z["d"] * z["q_width"] + 2 * z["d"] * z["kv_width"]


def expert_params(cfg: dict) -> int:
    """One routed expert's two matrices."""
    z = _sizes(cfg)
    return 2 * z["d"] * z["expert"]


def shared_expert_params(cfg: dict) -> int:
    z = _sizes(cfg)
    return 2 * z["d"] * z["shared"]


def block_dense_bytes(cfg: dict) -> float:
    """Everything outside the routed experts, over all blocks: matrices in
    bfloat16, vectors (norms, ``dt_bias``, ``A_log``, ``D``, the taps and
    their bias, the gated norm, the router and its bias) in float32."""
    z = _sizes(cfg)
    m_vectors = z["d"] + 3 * z["heads"] + (z["taps"] + 1) * z["conv_dim"] \
        + z["inner"]
    e_vectors = z["d"] + z["d"] * z["published"] + z["published"]
    return (z["m_blocks"] * (2.0 * mamba_matrix_params(cfg)
                             + 4.0 * m_vectors)
            + z["a_blocks"] * (2.0 * attention_matrix_params(cfg)
                               + 4.0 * z["d"])
            + z["e_blocks"] * (2.0 * shared_expert_params(cfg)
                               + 4.0 * e_vectors))


def expected_active_share(cfg: dict, lanes: int) -> float:
    """Share of the held experts that receive at least one of ``lanes``
    tokens under even routing over the published experts."""
    z = _sizes(cfg)
    return 1.0 - (1.0 - z["k"] / z["published"]) ** lanes


def decode_weight_bytes(cfg: dict) -> float:
    """Weights one decode step has to read at the configuration's lanes."""
    z = _sizes(cfg)
    lanes = cfg["serving"]["num_slots"]
    experts = z["e_blocks"] * z["held"] * 2.0 * expert_params(cfg) \
        * expected_active_share(cfg, lanes)
    head = 2.0 * z["d"] * cfg["vocab_size"] + 4.0 * z["d"]
    return block_dense_bytes(cfg) + experts + head


def state_bytes_per_lane(cfg: dict) -> float:
    """One lane's recurrent state (float32) and convolution tail
    (bfloat16), over all state-space blocks."""
    z = _sizes(cfg)
    return z["m_blocks"] * (4.0 * z["heads"] * z["p"] * z["n"]
                            + 2.0 * (z["taps"] - 1) * z["conv_dim"])


def cache_bytes_per_position(cfg: dict) -> float:
    """Keys and values one context position holds over the attention
    blocks, bfloat16."""
    z = _sizes(cfg)
    return z["a_blocks"] * 2.0 * 2 * z["kv_width"]


def decode_step_bytes(cfg: dict, context_positions: float) -> float:
    """Bytes one decode step needs to move: the weights above, the cached
    lines of the ``context_positions`` positions its lanes really hold
    (summed over lanes), and each lane's state read and written once a
    state-space block. The lanes are the configuration's: a step with
    fewer moves less state, so this is the floor of the full step."""
    lanes = cfg["serving"]["num_slots"]
    return decode_weight_bytes(cfg) \
        + context_positions * cache_bytes_per_position(cfg) \
        + 2.0 * lanes * state_bytes_per_lane(cfg)


def scan_flops_per_token(cfg: dict) -> float:
    """The chunked scan's own products for one token of one state-space
    block: ``C . B`` against the chunk's positions (half of them causal),
    the weighted sum of the chunk's inputs (half), what the token adds to
    the chunk's state, and what it reads of the state that entered."""
    z = _sizes(cfg)
    q = z["chunk"]
    return q * z["groups"] * z["n"] + q * z["inner"] \
        + 2 * 2 * z["inner"] * z["n"]


def prefill_flops(cfg: dict, tokens: float, squares: float = None) -> float:
    """Operations the prefill of prompts of ``tokens`` real tokens in all
    needs (``squares``: the sum of their squared lengths, for the causal
    attention; one prompt of ``tokens`` where not given). The head is one
    row a prompt and left out."""
    z = _sizes(cfg)
    squares = tokens * tokens if squares is None else squares
    per_token = (
        z["m_blocks"] * (2.0 * mamba_matrix_params(cfg)
                         + scan_flops_per_token(cfg)
                         + 2.0 * z["taps"] * z["conv_dim"])
        + z["a_blocks"] * 2.0 * attention_matrix_params(cfg)
        + z["e_blocks"] * (2.0 * shared_expert_params(cfg)
                           + 2.0 * z["d"] * z["published"]
                           + 2.0 * expert_params(cfg) * z["k"] * z["held"]
                           / z["published"]))
    # scores and weighted sums over the causal half: 2 * 2 * q_width / 2
    attention = z["a_blocks"] * 2.0 * z["q_width"] * squares
    return tokens * per_token + attention
