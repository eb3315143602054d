"""GQA attention: flash-style kv-block scan (pure jnp) + decode path.

Design (see DESIGN.md §5):
  * one scan over KV blocks with online softmax; the block body is
    ``jax.checkpoint``-ed so reverse-mode AD recomputes the (B,H,Sq,kc)
    probability blocks instead of storing them (the jnp analogue of the
    flash-attention backward; the Pallas kernel in kernels/flash_attention
    is the TPU fast path);
  * K/V heads are broadcast to the query-head count *inside* the block
    (repeat-KV), so the query tensor keeps its flat (B, S, H, hd) layout and
    can be sharded on H — or, when H doesn't divide the model axis, on S
    (q-sequence sharding with replicated KV). The choice is made by
    ``qshard_kind`` in lm._attn_apply.
  * masking is position-based, so the same code serves causal LM, encoder
    (bidirectional) and VLM prefixes. Fully-masked future blocks are
    computed-then-masked (2x causal-useful FLOPs) — the Pallas kernel skips
    them; accounted in the roofline's useful_ratio.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _repeat_kv(k, n_heads: int):
    """(B, S, K, hd) -> (B, S, H, hd) by broadcasting each kv head G times."""
    B, S, K, hd = k.shape
    G = n_heads // K
    if G == 1:
        return k
    k = jnp.broadcast_to(k[:, :, :, None, :], (B, S, K, G, hd))
    return k.reshape(B, S, n_heads, hd)


def chunked_attention(q, k, v, *, causal: bool, q_positions, kv_positions,
                      k_chunk: int = 1024, q_chunk: int = 0):
    """q: (B, Sq, H, hd); k, v: (B, Skv, K, hd). Returns (B, Sq, H, hd).

    ``q_chunk`` is accepted for knob compatibility; the q dimension is kept
    whole (it is sharded spatially instead — see module docstring).
    """
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    scale = hd ** -0.5

    k_chunk = min(k_chunk, Skv)
    while Skv % k_chunk:
        k_chunk //= 2
    nk = Skv // k_chunk

    K = k.shape[2]
    kc = k.reshape(B, nk, k_chunk, K, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, k_chunk, K, hd).transpose(1, 0, 2, 3, 4)
    kpos = kv_positions.reshape(B, nk, k_chunk).transpose(1, 0, 2)

    qf = q.astype(jnp.bfloat16)

    @jax.checkpoint
    def kv_block(carry, kin):
        m, l, acc = carry
        kb, vb, kp = kin                                    # (B,kc,K,hd),(B,kc)
        kb = _repeat_kv(kb, H)                              # block-local expand
        vb = _repeat_kv(vb, H)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            mask = q_positions[:, None, :, None] >= kp[:, None, None, :]
        else:
            mask = (kp >= 0)[:, None, None, :]
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vb.dtype), vb,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(kv_block, (m0, l0, a0), (kc, vc, kpos))
    out = acc / jnp.maximum(l, 1e-30)[..., None]            # (B,H,Sq,hd)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def gather_blocks(pool, block_tables):
    """Head-major block pool (NB, K, bs, hd) + tables (B, MB) -> the dense
    logical cache (B, MB*bs, K, hd) those table columns address."""
    B, MB = block_tables.shape
    _, K, bs, hd = pool.shape
    g = pool[block_tables]                                  # (B,MB,K,bs,hd)
    return g.transpose(0, 1, 3, 2, 4).reshape(B, MB * bs, K, hd)


def paged_decode_attention(q, k_pool, v_pool, block_tables, *, pos,
                           ctx_cols: int = 0):
    """Attention of S query tokens over a *paged* KV cache, block at a time.

    q: (B, S, H, hd); k_pool, v_pool: (NB, K, bs, hd) head-major physical
    blocks;
    block_tables: (B, MB) physical block per logical block; pos: (B,)
    logical position of the first query token (query j sits at pos + j,
    so S=1 is single-token decode and S>1 is multi-token chunked decode,
    e.g. suffix prefill against shared prefix blocks).

    ``ctx_cols`` (static; 0 = all MB) is the *visible* table prefix: the
    serving engine tracks every slot's write position on the host and
    compiles the decode step per context bucket (the same shape-bucketing
    it already applies to prefill), so a short batch attends over 2 table
    columns instead of all MB — the paged-attention savings with zero
    runtime control flow.  On TPU this dispatches to the Pallas kernel in
    kernels/paged_attention, whose chunk axis spans the visible prefix and
    which copies only each request's live blocks (online softmax streamed
    across chunks in VMEM — no dense gather at all); the CPU fallback
    gathers the visible blocks and runs one fused masked attention over
    them (numerics identical to the full-width gather path: masked tails
    contribute exp(-inf) = 0).
    """
    if jax.default_backend() == "tpu":
        from repro.kernels.paged_attention import paged_attention_op
        return paged_attention_op(q, k_pool, v_pool, block_tables, pos,
                                  ctx_cols=ctx_cols)

    B, S, H, hd = q.shape
    bs = k_pool.shape[2]
    MB = block_tables.shape[1]
    w = min(ctx_cols, MB) if ctx_cols else MB   # visible table columns
    bt = block_tables[:, :w]
    scale = hd ** -0.5
    qf = q.astype(jnp.bfloat16)
    q_pos = pos[:, None] + jnp.arange(S)[None, :]           # (B, S)
    kb = _repeat_kv(gather_blocks(k_pool, bt), H)
    vb = _repeat_kv(gather_blocks(v_pool, bt), H)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb,
                   preferred_element_type=jnp.float32) * scale
    kvp = jnp.arange(w * bs)
    mask = kvp[None, None, None, :] <= q_pos[:, None, :, None]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vb.dtype), vb,
                     preferred_element_type=jnp.float32)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)        # (B,S,H,hd)


def decode_attention(q, k_cache, v_cache, *, pos):
    """Attention of S query tokens over a KV cache.

    q: (B, S, H, hd); caches: (B, Smax, K, hd); pos: (B,) logical position
    of the *first* query token (query j sits at pos + j, so S=1 is the
    classic single-token decode and S>1 is chunked prefill against a prior
    cache).  The cache seq dim may be sharded (model axis); the softmax
    reductions then lower to partial-reduce + all-reduce.
    """
    B, S, H, hd = q.shape
    scale = hd ** -0.5
    kh = _repeat_kv(k_cache, H)
    vh = _repeat_kv(v_cache, H)
    s = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.bfloat16), kh,
                   preferred_element_type=jnp.float32) * scale
    kv_pos = jnp.arange(k_cache.shape[1])
    q_pos = pos[:, None] + jnp.arange(S)[None, :]           # (B, S)
    mask = kv_pos[None, None, :] <= q_pos[:, :, None]       # (B, S, Smax)
    s = jnp.where(mask[:, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqs,bshd->bhqd", p.astype(vh.dtype), vh,
                     preferred_element_type=jnp.float32)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)        # (B,S,H,hd)
