"""Bytes and operations the ``xing4`` decoder (latent attention, a leading
dense layer, sigmoid-routed experts with a shared one, a residual of
``hc_mult`` streams under manifold-constrained hyper-connections) *needs*,
from the configuration's shapes alone (the source's keys; the experts and
the vocabulary are the chip's share, as the file states them).

A decode step is bound by bytes. What it cannot avoid reading: every matrix
outside the routed experts once, in bfloat16 (norm vectors, the router and
its bias, and the hyper-connection mappings' parameters in float32); the
chip's slice of the head; the held experts that receive a token (``1 - (1 -
k / E)^lanes`` of them under even routing: 98.4 % at 64 lanes, 4 of 64); the
cached line of every position its lanes really hold (the latent and the one
rotary key, bfloat16: not the padding a stored line carries); and each
lane's float32 streams read and written once around every sub-layer (the
mapping, the mix into the sub-layer's input and the update can all be done
in that one pass). The configuration's ``num_slots`` lanes are taken to
decode. The embedding rows of the step's tokens are left out.

A prefill is bound by operations: per real token the projections, the dense
or shared-and-routed expert products, the router, and around every
sub-layer the mapping (its projection onto ``2 n + n^2`` values, the norm,
the mixes and the Sinkhorn iterations); over the causal half of every pair
of positions the attention's two products. The head is one row a prompt
and left out.
"""

from __future__ import annotations


def attention_params(cfg: dict) -> int:
    """Latent attention's five matrices, a layer."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    q_rank, kv_rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return (d * q_rank + q_rank * heads * (nope + rope)
            + d * (kv_rank + rope) + kv_rank * heads * (nope + v)
            + heads * v * d)


def dense_mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices (a shared expert's too)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def published_experts(cfg: dict) -> int:
    return cfg["n_routed_experts"] * cfg["expert_share"]["of"]


def hc_params(cfg: dict) -> int:
    """One sub-layer's mapping: ``phi`` of ``n C x (2 n + n^2)``, the
    biases and the three scalars (float32)."""
    n = cfg["hc_mult"]
    return (n * cfg["hidden_size"] + 1) * (2 * n + n * n) + 3


def _layers(cfg: dict):
    """``(dense layers, expert layers, sub-layers)`` held."""
    dense = cfg["first_k_dense_replace"]
    layers = cfg["num_hidden_layers"]
    return dense, layers - dense, 2 * layers


def expected_active_share(cfg: dict, lanes: int) -> float:
    """Share of the held experts that receive at least one of ``lanes``
    tokens under even routing over the published experts."""
    return 1.0 - (1.0 - cfg["num_experts_per_tok"]
                  / published_experts(cfg)) ** lanes


def dense_part_bytes(cfg: dict) -> float:
    """Everything outside the routed experts and the tables, over all
    layers: matrices bfloat16; norms, routers with their biases and the
    mappings float32."""
    d = cfg["hidden_size"]
    dense, sparse, sublayers = _layers(cfg)
    layers = dense + sparse
    vectors = layers * (cfg["q_lora_rank"] + cfg["kv_lora_rank"] + 2 * d)
    router = (d + 1) * published_experts(cfg)
    return 2.0 * layers * attention_params(cfg) + 4.0 * vectors \
        + dense * 2.0 * dense_mlp_params(cfg) \
        + sparse * (2.0 * expert_params(cfg) * cfg["n_shared_experts"]
                    + 4.0 * router) \
        + sublayers * 4.0 * hc_params(cfg)


def table_bytes(cfg: dict) -> float:
    """One of the two tables (embedding, head): the chip's slice."""
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def weight_bytes(cfg: dict) -> float:
    """Everything the chip holds, as served."""
    _, sparse, _ = _layers(cfg)
    return dense_part_bytes(cfg) + 2 * table_bytes(cfg) \
        + 4.0 * cfg["hidden_size"] \
        + sparse * cfg["n_routed_experts"] * 2.0 * expert_params(cfg)


def decode_weight_bytes(cfg: dict) -> float:
    """Weights one decode step has to read at the configuration's lanes:
    everything outside the routed experts once, the head's slice with the
    final norm, and the held experts expected to be active."""
    _, sparse, _ = _layers(cfg)
    lanes = cfg["serving"]["num_slots"]
    experts = sparse * cfg["n_routed_experts"] * 2.0 * expert_params(cfg) \
        * expected_active_share(cfg, lanes)
    return dense_part_bytes(cfg) + experts + table_bytes(cfg) \
        + 4.0 * cfg["hidden_size"]


def stream_bytes_per_token(cfg: dict) -> float:
    """A token's float32 streams read and written once around every
    sub-layer."""
    _, _, sublayers = _layers(cfg)
    return sublayers * 2 * 4.0 * cfg["hc_mult"] * cfg["hidden_size"]


def cache_bytes_per_position(cfg: dict) -> float:
    """Cached bytes one context position holds over all layers: the
    latent and the one rotary key, bfloat16."""
    return 2.0 * cfg["num_hidden_layers"] \
        * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def pool_bytes_per_row(cfg: dict) -> float:
    """Bytes the pool stores a row: a line a position a layer, padded to
    whole 128-value tiles, bfloat16."""
    line = -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) // 128) * 128
    return 2.0 * cfg["num_hidden_layers"] * cfg["n_positions"] * line


def decode_step_bytes(cfg: dict, context_positions: float) -> float:
    """Bytes one decode step needs to move: the weights above, the streams
    of the configuration's lanes, and the cached lines of the
    ``context_positions`` positions its lanes really hold (summed over
    lanes). Not what the rectangular pool moves."""
    return decode_weight_bytes(cfg) \
        + cfg["serving"]["num_slots"] * stream_bytes_per_token(cfg) \
        + context_positions * cache_bytes_per_position(cfg)


def hc_flops_per_token(cfg: dict) -> float:
    """One token's mappings over all sub-layers: the projection onto ``2 n
    + n^2`` values, the norm (a square and an add a value, a scale a
    value), the mix into the input (``n C`` products and sums), the update
    (``n^2 C`` of them, and ``n C`` for the output's part) and the
    Sinkhorn iterations (a sum and a division a value of ``H_res``, twice
    an iteration)."""
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    _, _, sublayers = _layers(cfg)
    a_sublayer = (2.0 * n * c * (2 * n + n * n) + 3.0 * n * c
                  + 2.0 * n * c + 2.0 * n * n * c + 2.0 * n * c
                  + cfg["hc_sinkhorn_iters"] * 2 * 2.0 * n * n)
    return sublayers * a_sublayer


def prefill_flops(cfg: dict, tokens: float, squares: float = None) -> float:
    """Operations the prefill of prompts of ``tokens`` real tokens in all
    needs (``squares``: the sum of their squared lengths; one prompt of
    ``tokens`` where not given)."""
    squares = tokens * tokens if squares is None else squares
    dense, sparse, _ = _layers(cfg)
    held_per_token = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / published_experts(cfg)
    per_token = 2.0 * (
        (dense + sparse) * attention_params(cfg)
        + dense * dense_mlp_params(cfg)
        + sparse * (expert_params(cfg) * (cfg["n_shared_experts"]
                                          + held_per_token)
                    + cfg["hidden_size"] * published_experts(cfg))) \
        + hc_flops_per_token(cfg)
    # scores and weighted sums over the causal half of the square
    attended = (dense + sparse) * 2.0 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"]) * squares / 2.0
    return tokens * per_token + attended
