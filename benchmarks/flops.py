"""Operations a model's arithmetic requires, from its shapes alone.

Kept with the benchmark so that no PR that claims a gain can move the
numerator of a utilization. Counts what the forward and backward passes
*require*: recomputed operations (remat) are not counted, the embedding
lookup is a gather and costs nothing, attention is counted causal (a
query at position t reads t + 1 keys), and a multiply-add is two
operations.
"""

from __future__ import annotations


def matmul_params(model: dict) -> int:
    """Parameters that take part in a matrix multiplication per token:
    every block's projections and the output head (untied)."""
    d = model["hidden_size"]
    f = model["intermediate_size"]
    hq = model["num_attention_heads"] * model["head_dim"]
    hkv = model["num_key_value_heads"] * model["head_dim"]
    per_layer = d * hq + 2 * d * hkv + hq * d + 3 * d * f
    return model["num_hidden_layers"] * per_layer + d * model["vocab_size"]


def total_params(model: dict) -> int:
    d = model["hidden_size"]
    norms = model["num_hidden_layers"] * 2 * d + d
    return matmul_params(model) + model["vocab_size"] * d + norms


def forward_flops_per_token(model: dict, seq: int) -> float:
    """One forward pass over a sequence of ``seq`` tokens, per token."""
    hq = model["num_attention_heads"] * model["head_dim"]
    # QK^T and PV: 2 * 2 * hq operations per (query, visible key) pair;
    # a causal query sees (seq + 1) / 2 keys on average.
    attention = model["num_hidden_layers"] * 4 * hq * (seq + 1) / 2
    return 2.0 * matmul_params(model) + attention


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward plus backward (twice the forward): the usual 6N + attention
    figure of model FLOP/s utilization."""
    return 3.0 * forward_flops_per_token(model, seq)
