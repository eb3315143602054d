"""jit'd public wrapper for the paged-attention kernel.

Consumes the PagedKVPool layout directly: physical KV blocks
(NB, K, bs, hd) + per-request block tables (B, MB) + first-query
positions (B,).  The pool's int8-quantized KV layout (blockwise
fake-quant: values are stored dequantized in the pool dtype, see
ServingEngine._quant_exec) needs no special handling — the kernel reads
whatever the blocks hold; parity over quantized content is pinned by
tests/test_paged_attention.py.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.paged_attention.kernel import paged_attention


@functools.partial(jax.jit, static_argnames=("ctx_cols", "interpret"))
def paged_attention_op(q, k_pool, v_pool, block_tables, pos, *,
                       ctx_cols: int = 0, interpret: bool = False):
    return paged_attention(q, k_pool, v_pool, block_tables, pos,
                           ctx_cols=ctx_cols, interpret=interpret)
