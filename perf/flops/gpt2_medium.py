"""Operations and bytes the GPT-2 family *needs*, from shapes alone.

Conventions (the usual ones for model FLOP/s utilisation): a multiply-add
is two operations; only matrix multiplications count; attention is counted
over the whole ``t x t`` score matrix (what a dense implementation runs,
and what ``observability.count_flops`` reads off the jaxpr); a training
step is forward plus twice that for the backward pass; recomputation under
``remat`` does not count; the embedding lookup is not a multiplication.
"""

from __future__ import annotations


def forward_flops_per_token(cfg: dict, context: int) -> float:
    """Matmul operations for one token that attends over ``context``
    positions (``context = t`` for a full training sequence)."""
    d, inner = cfg["n_embd"], cfg["n_inner"]
    block = 2 * d * 3 * d + 2 * d * d + 2 * d * inner + 2 * inner * d
    attention = 2 * context * d + 2 * context * d      # q.k^T and p.v
    return cfg["n_layer"] * (block + attention) + 2 * d * cfg["vocab_size"]


def train_flops_per_sample(cfg: dict) -> float:
    """Forward and backward over one sequence of the training length."""
    t = cfg["train_data"]["sequence_length"]
    return 3.0 * t * forward_flops_per_token(cfg, t)


def train_flops_per_step(cfg: dict) -> float:
    """One optimizer step of one worker (``batch_size`` sequences)."""
    return cfg["trainer"]["batch_size"] * train_flops_per_sample(cfg)


def decode_weight_bytes(cfg: dict) -> float:
    """Bytes of weights one decode step has to read once, in the type each
    is used in: bfloat16 block matrices (the configuration's compute
    type), float32 head, biases and LayerNorm vectors; the embedding rows
    of the step's tokens are negligible and left out."""
    d, inner, v = cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"]
    block_matrices = d * 3 * d + d * d + d * inner + inner * d
    block_vectors = 3 * d + d + inner + d + 4 * d      # biases, two norms
    return (cfg["n_layer"] * (2.0 * block_matrices + 4.0 * block_vectors)
            + 4.0 * (d * v + v) + 4.0 * 2 * d)


def kv_bytes_per_position(cfg: dict) -> float:
    """Bytes of cached keys and values one context position holds, over
    all layers, in bfloat16."""
    return 2.0 * cfg["n_layer"] * cfg["n_embd"] * 2


def decode_step_bytes(cfg: dict, context_positions: float) -> float:
    """Bytes one decode step needs to move: the weights once, plus the keys
    and values of the ``context_positions`` positions its lanes really
    hold (summed over lanes). Not what the rectangular pool moves."""
    return decode_weight_bytes(cfg) + \
        context_positions * kv_bytes_per_position(cfg)
