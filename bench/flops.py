"""Operations and bytes the algorithm needs, from the configuration's shapes.

Counts are of required work: a causal query attends to the keys at or
before it, padding is not counted, and a prefill projects one row of
logits (the program computes only the last position's).  ``m`` is the
configuration file's ``model`` block.
"""
from __future__ import annotations


def layer_params(m: dict) -> int:
    D, H, K, hd, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                      m["head_dim"], m["d_ff"])
    return D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F


def matmul_flops_per_token(m: dict) -> int:
    """Projections and MLP of every layer, for one token."""
    return 2 * m["n_layers"] * layer_params(m)


def head_flops(m: dict) -> int:
    """One row of logits."""
    return 2 * m["d_model"] * m["vocab_size"]


def attn_flops(m: dict, keys: int) -> int:
    """QK^T and PV of one query over ``keys`` keys, all layers."""
    return 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * keys


def prefill_flops(m: dict, n: int, start: int = 0) -> int:
    """``n`` prompt tokens after ``start`` cached ones, one logits row."""
    keys = n * start + n * (n + 1) // 2       # query i sees start + i keys
    return (n * matmul_flops_per_token(m) + attn_flops(m, keys)
            + head_flops(m))


def decode_flops(m: dict, keys: int) -> int:
    """One decoded token that attends ``keys`` keys (itself included)."""
    return matmul_flops_per_token(m) + head_flops(m) + attn_flops(m, keys)


def kv_bytes(m: dict, keys: int, itemsize: int = 2) -> int:
    """K and V of ``keys`` positions, all layers, read once per KV head."""
    return (2 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"] * keys
            * itemsize)


def paged_attention_call(m: dict, queries: int, start: int) -> tuple:
    """(flops, bytes) of one request's paged attention over all layers:
    ``queries`` query tokens at positions start .. start+queries-1."""
    keys = queries * start + queries * (queries + 1) // 2
    q_out = 2 * 2 * m["n_layers"] * m["n_heads"] * m["head_dim"] * queries
    return attn_flops(m, keys), kv_bytes(m, start + queries) + q_out
