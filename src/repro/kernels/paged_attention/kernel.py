"""Pallas TPU paged-attention kernel (decode / chunked decode, forward).

Grid: (B, K, n_q, n_chunks) — one step per (request, KV head, query
tile, chunk of visible blocks), all sequential ("arbitrary"): v5e has one
TensorCore, so a parallel axis buys nothing, and a step's fixed cost is
what the chunk amortises.

- Query tile: all G = H/K query heads of the KV head's group, and with
  S > 1 their S query rows too, laid out s-major as (B, K, S*G, hd) rows
  ``s*G + g``, so each KV block is fetched once for the whole group.
  Where S*G exceeds ``_MAX_ROWS`` the rows are cut into n_q query tiles
  (the multi-token form at a suffix bucket of hundreds of tokens).
- KV chunk: ``C`` blocks of ``bs`` tokens (``_tiles``: about
  ``_CHUNK_TOKENS`` tokens, never more than the ``ctx_cols`` visible
  table prefix).  The pools stay in HBM (``memory_space=pl.ANY``); each
  block ``pool[tables[b, i], kh]`` is a contiguous (bs, hd) slab of the
  head-major (NB, K, bs, hd) pool, copied by hand into a double-buffered
  VMEM chunk.  Each live step starts the copies of the next live step
  before it computes, so a copy overlaps the previous chunk's compute.
- Live blocks only: query tile q of request b needs
  ``pos[b] + s_last + 1`` tokens, ``ceil(./bs)`` blocks.  A chunk wholly
  past that issues no copy and no compute; a partly live chunk copies
  only its live blocks (the rest of the buffer is zeroed in V and masked
  in the scores).  A free slot (pos 0, S = 1) costs one block.

Masking is logical-position based: kv position ``i*bs + off`` is visible
to query ``pos[b] + s`` iff it is <= the query position.  That one rule
covers (a) causality inside a multi-token chunk (S > 1 = chunked prefill
against shared prefix blocks), (b) partially filled tail blocks, and
(c) stale table rows — entries past a request's extent point at the
pool's trash block, whose logical positions are all in the future.

Precision: QK^T and PV accumulate in f32 (bf16 K meets bf16 queries on
the MXU as is: bf16 products are exact in f32); the online softmax
(m, l, acc) and p stay f32, V is widened to meet p.  The ``block_size``
knob of the serving pool is the kernel's DMA unit — the tuner picks it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_CHUNK_TOKENS = 512     # KV tokens per grid step
_MAX_ROWS = 512         # query rows per tile (G * S cut above this)


def _tiles(bs: int, n_vis: int, rows: int):
    """(blocks per chunk C, query rows per tile tq) from the shapes alone.

    C: about ``_CHUNK_TOKENS`` tokens, never more than the visible prefix.
    tq: all G*S rows up to ``_MAX_ROWS``, else ``_MAX_ROWS`` (a multiple
    of every sublane tile), so the (tq, C*bs) f32 score tile stays at
    most 1 MiB of VMEM whatever the suffix bucket."""
    c = max(1, min(n_vis, _CHUNK_TOKENS // bs))
    tq = rows if rows <= _MAX_ROWS else _MAX_ROWS
    return c, tq


def _paged_kernel(tables_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                  kbuf, vbuf, ksem, vsem, slot_ref, m_ref, l_ref, acc_ref,
                  *, sm_scale, bs, C, n_vis, S, G, tq):
    b, h, qi, c = (pl.program_id(i) for i in range(4))
    B, K, nq, nc = (pl.num_programs(i) for i in range(4))
    T = C * bs

    def n_live(b, qi):
        # blocks holding a position some row of query tile qi can see
        s_last = jnp.minimum(((qi + 1) * tq - 1) // G, S - 1)
        # at least one: chunk 0 is always live, which the prefetch of the
        # next step relies on
        return jnp.clip((pos_ref[b] + s_last) // bs + 1, 1, n_vis)

    def n_copy(b, qi, c):
        return jnp.clip(n_live(b, qi) - c * C, 0, C)

    def copies(b, h, qi, c, slot, action):
        """``action`` ("start" or "wait") each live-block copy of chunk c
        of (b, h, qi) into buffer ``slot``."""
        def body(i, _):
            blk = tables_ref[b, c * C + i]
            for hbm, buf, sem in ((k_hbm, kbuf, ksem), (v_hbm, vbuf, vsem)):
                cp = pltpu.make_async_copy(hbm.at[blk, h], buf.at[slot, i],
                                           sem.at[slot])
                getattr(cp, action)()
        jax.lax.fori_loop(0, n_copy(b, qi, c), body, None)

    @pl.when((b == 0) & (h == 0) & (qi == 0) & (c == 0))
    def _first():
        slot_ref[0] = 0
        copies(b, h, qi, c, 0, "start")

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = n_copy(b, qi, c)

    @pl.when(live > 0)
    def _body():
        slot = slot_ref[0]
        # the next live step: this tile's next chunk, else chunk 0 (always
        # live) of the next (request, head, query tile) in grid order
        more = (c + 1 < nc) & (n_copy(b, qi, c + 1) > 0)
        q_next = qi + 1 < nq
        h_next = h + 1 < K
        b_next = b + 1 < B
        nb = jnp.where(more | q_next | h_next, b, b + 1)
        nh = jnp.where(more | q_next, h, jnp.where(h_next, h + 1, 0))
        nqi = jnp.where(more, qi, jnp.where(q_next, qi + 1, 0))
        ncc = jnp.where(more, c + 1, 0)

        @pl.when(more | q_next | h_next | b_next)
        def _prefetch():
            copies(nb, nh, nqi, ncc, 1 - slot, "start")
            slot_ref[0] = 1 - slot

        copies(b, h, qi, c, slot, "wait")

        kv_pos = c * T + jax.lax.broadcasted_iota(jnp.int32, (tq, T), 1)
        row = qi * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, T), 0)
        q_pos = pos_ref[b] + jnp.minimum(row // G, S - 1)

        q = q_ref[...]
        k = kbuf[slot]                                 # (C, bs, hd)
        dt = jnp.promote_types(q.dtype, k.dtype)
        k = k.astype(dt).reshape(T, -1)
        s = jax.lax.dot_general(q.astype(dt), k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                               # (tq, T)
        s = jnp.where(kv_pos <= q_pos, s, NEG_INF)     # tail/causal/stale
        v = vbuf[slot].astype(jnp.float32).reshape(T, -1)
        # rows past the copied blocks hold whatever the buffer had
        v_row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(v_row < live * bs, v, 0.0)
        m_prev = m_ref[:, :1]                          # (tq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(c == nc - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, block_tables, pos, *,
                    ctx_cols: int = 0, interpret: bool = False):
    """Attention of S query tokens per request over a paged KV cache.

    q: (B, S, H, hd); k_pool, v_pool: (NB, K, bs, hd) head-major physical
    blocks with H % K == 0; block_tables: (B, MB) int32 physical block per
    logical block; pos: (B,) int32 logical position of the *first* query
    token (query j of request b sits at pos[b] + j — S=1 is single-token
    decode, S>1 is chunked decode against a prior cache).  ``ctx_cols``
    (static; 0 = all MB) bounds the visible table prefix: the chunk axis
    covers only it, and within it only each request's live blocks are
    fetched.  Returns (B, S, H, hd) in q.dtype.  Numerically equivalent
    to gathering the table into a dense cache and running full-softmax
    attention (ref.py).
    """
    B, S, H, hd = q.shape
    NB, K, bs, _ = k_pool.shape
    MB = block_tables.shape[1]
    n_vis = min(ctx_cols, MB) if ctx_cols else MB
    G = H // K
    rows = S * G
    C, tq = _tiles(bs, n_vis, rows)
    nq = -(-rows // tq)
    nc = -(-n_vis // C)

    # (B, S, K*G, hd) -> (B, K, S*G, hd): row s*G + g is query s, head kh*G+g
    qg = q.reshape(B, S, K, G, hd).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(B, K, rows, hd)
    if nq * tq != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, nq * tq - rows), (0, 0)))
    tables = block_tables.astype(jnp.int32)
    pos = pos.astype(jnp.int32)

    def q_index(b, h, qi, c, tables_ref, pos_ref):
        return (b, h, qi, 0)

    kernel = functools.partial(
        _paged_kernel, sm_scale=hd ** -0.5, bs=bs, C=C, n_vis=n_vis, S=S,
        G=G, tq=tq)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, nq, nc),
        in_specs=[
            pl.BlockSpec((None, None, tq, hd), q_index),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, None, tq, hd), q_index),
        scratch_shapes=[
            pltpu.VMEM((2, C, bs, hd), k_pool.dtype),   # K chunks
            pltpu.VMEM((2, C, bs, hd), v_pool.dtype),   # V chunks
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),                # buffer in use
            pltpu.VMEM((tq, 128), jnp.float32),         # m
            pltpu.VMEM((tq, 128), jnp.float32),         # l
            pltpu.VMEM((tq, hd), jnp.float32),          # acc
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, nq * tq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 4),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(tables, pos, qg, k_pool, v_pool)
    out = out[:, :, :rows].reshape(B, K, S, G, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, S, H, hd)
