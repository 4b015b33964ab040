"""Bytes and operations the ``dots3_note`` decoder (full latent layers under a
learned sparse indexer, window latent layers at sizes of their own) *needs*,
from the configuration's shapes alone (the source's keys; the experts and
the vocabulary are the chip's share, as the file states them). The same work
whatever implements it: a selection that is skipped, or taken and then
attended densely under a mask, a window attended as a square, or a ring read
whole, reads and multiplies more than this and shows a lower share.

A decode step is bound by bytes. What it cannot avoid reading: every matrix
outside the routed experts once, in bfloat16 (norm vectors, the index key's
LayerNorm, the router and its bias in float32); the chip's slice of the
head; the held experts that receive a token (``1 - (1 - k / E)^lanes`` of
them under even routing: 63.8 % at 32 lanes, 8 of 256); and from the cache,
a full layer, the index key of EVERY position a lane holds (the index score
is over all of them) but the latent line of the ``index_topk`` chosen
positions only; a window layer, the lines of the ``sliding_window_size``
latest positions (all of them while a lane holds fewer). Lines and keys are
counted as the values they hold, bfloat16, not the padding a stored line
carries. The embedding rows of the step's tokens are left out.

A prefill is bound by operations: per real token the projections, the
indexer's, the gates', the dense or shared-and-routed expert products; over
the causal half of every pair of positions a full layer's index score; and
the attention's two products over the positions a query attends: ``min(p +
1, index_topk)`` at position ``p`` of a full layer, ``min(p + 1, window)``
of a window layer, not all that precede it.
"""

from __future__ import annotations


def _kinds(cfg: dict):
    """``(full layers, window layers)`` of the layers held."""
    full = cfg["layer_types"].count("full_attention")
    return full, len(cfg["layer_types"]) - full


def attention_params(cfg: dict, pre: str = "") -> int:
    """Latent attention's five matrices and its headwise gate, a layer of
    the kind ``pre`` names (``""`` full, ``"swa_"`` window)."""
    d, heads = cfg["hidden_size"], cfg[pre + "num_attention_heads"]
    q_rank, kv_rank = cfg[pre + "q_lora_rank"], cfg[pre + "kv_lora_rank"]
    nope, rope, v = (cfg[pre + "qk_nope_head_dim"],
                     cfg[pre + "qk_rope_head_dim"], cfg[pre + "v_head_dim"])
    return (d * q_rank + q_rank * heads * (nope + rope)
            + d * (kv_rank + rope) + kv_rank * heads * (nope + v)
            + heads * v * d + d * heads)


def indexer_params(cfg: dict) -> int:
    """The indexer's three matrices, a full layer (its LayerNorm's ``2 *
    index_head_dim`` float32 values are counted with the vectors)."""
    heads, dim = cfg["index_n_heads"], cfg["index_head_dim"]
    return cfg["q_lora_rank"] * heads * dim + cfg["hidden_size"] * (dim
                                                                    + heads)


def dense_mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices (a shared expert's too)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def published_experts(cfg: dict) -> int:
    return cfg["n_routed_experts"] * cfg["expert_share"]["of"]


def expected_active_share(cfg: dict, lanes: int) -> float:
    """Share of the held experts that receive at least one of ``lanes``
    tokens under even routing over the published experts."""
    return 1.0 - (1.0 - cfg["num_experts_per_tok"]
                  / published_experts(cfg)) ** lanes


def _layers(cfg: dict):
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense


def dense_part_bytes(cfg: dict) -> float:
    """Everything outside the routed experts and the tables, over all
    layers: matrices bfloat16; norms, the index key's LayerNorm, routers
    and their biases float32."""
    d = cfg["hidden_size"]
    dense, sparse = _layers(cfg)
    full, window = _kinds(cfg)
    attention = full * (attention_params(cfg) + indexer_params(cfg)) \
        + window * attention_params(cfg, "swa_")
    vectors = full * (cfg["q_lora_rank"] + cfg["kv_lora_rank"]
                      + 2 * cfg["index_head_dim"]) \
        + window * (cfg["swa_q_lora_rank"] + cfg["swa_kv_lora_rank"]) \
        + (full + window) * 2 * d
    router = (d + 1) * published_experts(cfg)
    return 2.0 * attention + 4.0 * vectors \
        + dense * 2.0 * dense_mlp_params(cfg) \
        + sparse * (2.0 * expert_params(cfg) * cfg["n_shared_experts"]
                    + 4.0 * router)


def table_bytes(cfg: dict) -> float:
    """One of the two tables (embedding, head): the chip's slice."""
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def weight_bytes(cfg: dict) -> float:
    """Everything the chip holds, as served."""
    _, sparse = _layers(cfg)
    return dense_part_bytes(cfg) + 2 * table_bytes(cfg) \
        + 4.0 * cfg["hidden_size"] \
        + sparse * cfg["n_routed_experts"] * 2.0 * expert_params(cfg)


def decode_weight_bytes(cfg: dict) -> float:
    """Weights one decode step has to read at the configuration's lanes:
    everything outside the routed experts once, the head's slice with the
    final norm, and the held experts expected to be active."""
    _, sparse = _layers(cfg)
    lanes = cfg["serving"]["num_slots"]
    experts = sparse * cfg["n_routed_experts"] * 2.0 * expert_params(cfg) \
        * expected_active_share(cfg, lanes)
    return dense_part_bytes(cfg) + experts + table_bytes(cfg) \
        + 4.0 * cfg["hidden_size"]


def pool_bytes_per_row(cfg: dict) -> float:
    """Bytes the pool stores a row: a full layer's lines (padded to whole
    128-value tiles) and index keys at every position, a window layer's
    ring; bfloat16."""
    tiles = lambda n: -(-n // 128) * 128
    full, window = _kinds(cfg)
    line = tiles(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    ring = tiles(cfg["swa_kv_lora_rank"] + cfg["swa_qk_rope_head_dim"])
    return 2.0 * (full * cfg["n_positions"] * (line + cfg["index_head_dim"])
                  + window * cfg["serving"]["ring_cells"] * ring)


def decode_step_bytes(cfg: dict, context_positions: float) -> float:
    """Bytes one decode step needs to move: the weights above; a full
    layer, the index key of each of the ``context_positions`` positions its
    lanes hold (summed over lanes) and the latent line of the positions
    they select, ``index_topk`` a lane; a window layer, the lines of the
    ``sliding_window_size`` latest positions a lane. Both capped by what
    the lanes hold: exact where every lane is past ``index_topk`` (every
    lane of a cell whose prompts are longer), an upper bound on the lines
    where some slots are empty."""
    lanes = cfg["serving"]["num_slots"]
    full, window = _kinds(cfg)
    key = 2.0 * cfg["index_head_dim"]
    line = 2.0 * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    ring = 2.0 * (cfg["swa_kv_lora_rank"] + cfg["swa_qk_rope_head_dim"])
    selected = min(context_positions, float(lanes * cfg["index_topk"]))
    recent = min(context_positions,
                 float(lanes * cfg["sliding_window_size"]))
    return decode_weight_bytes(cfg) \
        + full * (key * context_positions + line * selected) \
        + window * ring * recent


def attended_pairs(k: float, tokens: float, squares: float) -> float:
    """Pairs (query, attended position) over prompts of ``tokens`` real
    tokens in all and ``squares`` summed squared lengths, where a query at
    position ``p`` attends ``min(p + 1, k)``: a prompt of ``n > k`` tokens
    has ``n k - k^2 / 2`` (``n^2 / 2`` below ``k``). The number of prompts
    is not handed over and is taken as ``tokens^2 / squares`` (exact for
    equal lengths; 1 % under at the cell's sigma of 0.1)."""
    if squares <= 0 or squares / tokens <= k:
        return squares / 2.0
    return k * tokens - tokens * tokens / squares * k * k / 2.0


def prefill_flops(cfg: dict, tokens: float, squares: float = None) -> float:
    """Operations the prefill of prompts of ``tokens`` real tokens in all
    needs (``squares``: the sum of their squared lengths; one prompt of
    ``tokens`` where not given). The head is one row a prompt and left
    out."""
    squares = tokens * tokens if squares is None else squares
    dense, sparse = _layers(cfg)
    full, window = _kinds(cfg)
    held_per_token = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / published_experts(cfg)
    per_token = 2.0 * (
        full * (attention_params(cfg) + indexer_params(cfg))
        + window * attention_params(cfg, "swa_")
        + dense * dense_mlp_params(cfg)
        + sparse * (expert_params(cfg) * (cfg["n_shared_experts"]
                                          + held_per_token)
                    + cfg["hidden_size"] * published_experts(cfg)))
    # the index score over the causal half: heads x dim products a pair
    index = full * cfg["index_n_heads"] * cfg["index_head_dim"] * squares
    # scores and weighted sums over the positions a query attends
    products = lambda pre: 2.0 * cfg[pre + "num_attention_heads"] * (
        cfg[pre + "qk_nope_head_dim"] + cfg[pre + "qk_rope_head_dim"]
        + cfg[pre + "v_head_dim"])
    attended = full * products("") * attended_pairs(
        float(cfg["index_topk"]), tokens, squares) \
        + window * products("swa_") * attended_pairs(
            float(cfg["sliding_window_size"]), tokens, squares)
    return tokens * per_token + index + attended
