"""Pallas paged-attention decode kernel (vLLM-style block-table attention).

The serving engine's PagedKVPool stores KV in fixed-size physical blocks
addressed through per-request block tables.  This kernel consumes that
layout *in place*: the block table is a scalar-prefetch operand and the
pools stay in HBM, so each live block is copied by hand straight from
its physical slot — the dense gather-then-attend sequence (materializing
(B, MB*bs, K, hd) copies of the cache every layer, every decode step)
disappears from the hot path.

kernel.py  pl.pallas_call grid (requests, KV heads, query tiles, chunks
           of blocks): a KV head's whole query group in one tile, each
           live block fetched once (double-buffered), chunks past a
           request's live extent skipped; online softmax across chunks
ref.py     pure-jnp oracle: dense gather + full-softmax attention (the
           pre-kernel serving path, kept as the parity baseline)
ops.py     jit'd wrappers, one per form: decode (S=1) and multi-token
           (S>1), each naming its custom call (interpret-mode on CPU)

The jnp execution schedule used on CPU lives in
repro.models.attention.paged_decode_attention (one masked attention over
the visible blocks) — models/ stays importable without Pallas.
"""
from repro.kernels.paged_attention.kernel import paged_attention
from repro.kernels.paged_attention.ops import (paged_attention_op,
                                               paged_attention_op_decode,
                                               paged_attention_op_multi)
from repro.kernels.paged_attention.ref import paged_attention_ref

__all__ = ["paged_attention", "paged_attention_op",
           "paged_attention_op_decode", "paged_attention_op_multi",
           "paged_attention_ref"]
