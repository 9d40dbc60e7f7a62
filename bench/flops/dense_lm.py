"""Operations and bytes of the dense decoder, counted from its shapes.

Model FLOPs count the matrix products the algorithm needs (two operations
per multiply-add) and causal attention at half the square; no recomputation.
"""

from __future__ import annotations

BF16 = 2


def layer_matmul_params(d: dict) -> int:
    qo = 2 * d["d_model"] * d["num_heads"] * d["head_dim"]
    kv = 2 * d["d_model"] * d["num_kv_heads"] * d["head_dim"]
    mlp = (3 if d["mlp"] == "swiglu" else 2) * d["d_model"] * d["d_ff"]
    return qo + kv + mlp


def body_params(d: dict) -> int:
    return d["num_layers"] * layer_matmul_params(d)


def head_params(d: dict) -> int:
    return d["d_model"] * d["vocab_size"]


def attn_width(d: dict) -> int:
    return d["num_heads"] * d["head_dim"]


def train_flops_per_token(d: dict, seq: int) -> float:
    """Forward and backward: 6 per matmul parameter (the head included, the
    embedding gather not), plus causal attention 6 * S * H * hd per layer."""
    return 6.0 * (body_params(d) + head_params(d)) + 6.0 * seq * attn_width(d) * d["num_layers"]


def prefill_flops(d: dict, n: int) -> float:
    """A prompt of n tokens: the body's products and causal attention (the
    head runs for the last position only and is left out)."""
    return 2.0 * n * body_params(d) + 2.0 * attn_width(d) * n * n * d["num_layers"]


def decode_flops(d: dict, slots: int, live_sum: int) -> float:
    """One decode step of ``slots`` requests attending over ``live_sum``
    cached positions in all: body, head, attention."""
    return 2.0 * slots * (body_params(d) + head_params(d)) + 4.0 * attn_width(d) * live_sum * d["num_layers"]


def kv_bytes_per_token(d: dict) -> int:
    return d["num_layers"] * 2 * d["num_kv_heads"] * d["head_dim"] * BF16


def decode_bytes(d: dict, live_sum: int) -> float:
    """Least bytes of one decode step: every matmul weight once (the head
    included) and the live KV cache."""
    return BF16 * (body_params(d) + head_params(d)) + live_sum * kv_bytes_per_token(d)
