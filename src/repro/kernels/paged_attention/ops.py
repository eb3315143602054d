"""jit'd public wrappers for the paged-attention kernel.

Consumes the PagedKVPool layout directly: physical KV blocks
(NB, K, bs, hd) + per-request block tables (B, MB) + first-query
positions (B,).  The pool's int8-quantized KV layout (blockwise
fake-quant: values are stored dequantized in the pool dtype, see
ServingEngine._quant_exec) needs no special handling — the kernel reads
whatever the blocks hold; parity over quantized content is pinned by
tests/test_paged_attention.py.

The kernel's custom call takes its HLO name from the jitted wrapper, so
the two forms are two wrappers: ``paged_attention_op_decode`` for one
query token per request (decode) and ``paged_attention_op_multi`` for
several (suffix prefill after a shared prefix, speculative verify).  A
profiler trace then names each form, with no number the compiler picks.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.paged_attention.kernel import paged_attention


@functools.partial(jax.jit, static_argnames=("ctx_cols", "interpret"))
def paged_attention_op_decode(q, k_pool, v_pool, block_tables, pos, *,
                              ctx_cols: int = 0, interpret: bool = False):
    return paged_attention(q, k_pool, v_pool, block_tables, pos,
                           ctx_cols=ctx_cols, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("ctx_cols", "interpret"))
def paged_attention_op_multi(q, k_pool, v_pool, block_tables, pos, *,
                             ctx_cols: int = 0, interpret: bool = False):
    return paged_attention(q, k_pool, v_pool, block_tables, pos,
                           ctx_cols=ctx_cols, interpret=interpret)


def paged_attention_op(q, k_pool, v_pool, block_tables, pos, *,
                       ctx_cols: int = 0, interpret: bool = False):
    """Paged attention of ``q`` (B, S, H, hd), through the wrapper of its
    query width S."""
    op = (paged_attention_op_decode if q.shape[1] == 1
          else paged_attention_op_multi)
    return op(q, k_pool, v_pool, block_tables, pos, ctx_cols=ctx_cols,
              interpret=interpret)
