"""Pallas TPU paged-attention kernel (decode / chunked decode, forward).

Grid: (B*H, n_visible_blocks) — the kv axis spans the host-chosen
``ctx_cols`` visible prefix of the table (all of it when 0), so the
engine's context bucketing shrinks the grid itself rather than skipping
future blocks; the kv-block dimension is the innermost
sequential ("arbitrary") axis so the online-softmax state (m, l, acc)
lives in VMEM scratch across kv iterations — the flash_attention schedule
applied to a *paged* cache.  The per-request block table and write
positions are scalar-prefetch operands (pltpu.PrefetchScalarGridSpec):
the K/V index maps read ``tables[b, kb]`` to pick the physical block, so
the kernel walks the pool's indirection directly and no dense
(B, MB*bs, K, hd) gather is ever materialized.

Masking is logical-position based: kv position ``kb*bs + off`` is visible
to query ``pos[b] + j`` iff it is <= the query position.  That one rule
covers (a) causality inside a multi-token chunk (S > 1 = chunked prefill
against shared prefix blocks), (b) partially filled tail blocks, and
(c) stale table rows — entries past a request's extent point at the
pool's trash block, whose logical positions are all in the future.
Blocks entirely in the future of every query are *skipped* via pl.when
(the gather path computes-then-masks them).

GQA is handled in the K/V index maps: query head h reads kv head h // G,
so the kv pool is never expanded to H heads.  The pool is head-major,
(NB, K, bs, hd), so one step's KV tile is a whole (bs, hd) slab of one
head: its last two dims are the array's own, which the TPU lowering
accepts for any pool dtype and block size (a (bs, 1, hd) slice of a
token-major (NB, bs, K, hd) pool is refused when K > 1).  The
``block_size`` knob of the serving pool is the kernel's kv tile size — the
tuner picks the tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, sm_scale, bs, n_kb, S, H):
    bh = pl.program_id(0)
    kb = pl.program_id(1)
    b = bh // H

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p0 = pos_ref[b]                                    # first query position
    qp = p0 + jax.lax.broadcasted_iota(jnp.int32, (S, bs), 0)
    kvp = kb * bs + jax.lax.broadcasted_iota(jnp.int32, (S, bs), 1)

    def _body():
        q = q_ref[0].astype(jnp.float32)               # (S, hd)
        k = k_ref[...].astype(jnp.float32)             # (bs, hd)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                               # (S, bs)
        s = jnp.where(kvp <= qp, s, NEG_INF)           # tail/causal/stale mask
        m_prev = m_ref[:, :1]                          # (S, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    # skip blocks entirely in the future of this request's last query
    pl.when(kb * bs <= p0 + S - 1)(_body)

    @pl.when(kb == n_kb - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, block_tables, pos, *,
                    ctx_cols: int = 0, interpret: bool = False):
    """Attention of S query tokens per request over a paged KV cache.

    q: (B, S, H, hd); k_pool, v_pool: (NB, K, bs, hd) head-major physical
    blocks with H % K == 0; block_tables: (B, MB) int32 physical block per logical
    block; pos: (B,) int32 logical position of the *first* query token
    (query j of request b sits at pos[b] + j — S=1 is single-token decode,
    S>1 is chunked decode against a prior cache).  ``ctx_cols`` (static;
    0 = all MB) bounds the visible table prefix: the kv grid axis shrinks
    to it, so a short batch never iterates — or DMAs blocks for — table
    columns past the host-tracked context bucket (``pl.when`` still skips
    per-request future blocks *within* the bucket).  Returns (B, S, H, hd)
    in q.dtype.  Numerically equivalent to gathering the table into a
    dense cache and running full-softmax attention (ref.py).
    """
    B, S, H, hd = q.shape
    NB, K, bs, _ = k_pool.shape
    MB = block_tables.shape[1]
    n_vis = min(ctx_cols, MB) if ctx_cols else MB
    G = H // K

    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    tables = block_tables.astype(jnp.int32)
    pos = pos.astype(jnp.int32)

    def q_index(bh, kb, tables_ref, pos_ref):
        return (bh, 0, 0)

    def kv_index(bh, kb, tables_ref, pos_ref):
        b = bh // H
        h = bh % H
        return (tables_ref[b, kb], h // G, 0, 0)

    kernel = functools.partial(
        _paged_kernel, sm_scale=hd ** -0.5, bs=bs, n_kb=n_vis, S=S, H=H)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * H, n_vis),
        in_specs=[
            pl.BlockSpec((1, S, hd), q_index),
            pl.BlockSpec((None, None, bs, hd), kv_index),
            pl.BlockSpec((None, None, bs, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, S, hd), q_index),
        scratch_shapes=[
            pltpu.VMEM((S, 128), jnp.float32),   # m
            pltpu.VMEM((S, 128), jnp.float32),   # l
            pltpu.VMEM((S, hd), jnp.float32),    # acc
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tables, pos, qf, k_pool, v_pool)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
