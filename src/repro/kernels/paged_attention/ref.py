"""Pure-jnp oracle for the paged-attention kernel — and the pre-kernel
serving path: gather the block table into a dense cache, then run masked
full-softmax attention over it ("gather-then-dense-attention").

Kept bit-comparable to what ``models.lm._attn_apply`` did before the
kernel landed, so the parity tests pin three-way equivalence:
Pallas kernel == blocked jnp schedule == this gather path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def gather_kv(pool, block_tables):
    """(NB, K, bs, hd) + (B, MB) -> dense (B, MB*bs, K, hd): the logical
    view of each request's cache (stale table entries gather the trash
    block — their positions are masked by the caller)."""
    B, MB = block_tables.shape
    NB, K, bs, hd = pool.shape
    g = pool[block_tables]                                  # (B,MB,K,bs,hd)
    return g.transpose(0, 1, 3, 2, 4).reshape(B, MB * bs, K, hd)


def paged_attention_ref(q, k_pool, v_pool, block_tables, pos):
    """Same contract as kernel.paged_attention; fp32 softmax throughout.

    q: (B, S, H, hd); pools: (NB, K, bs, hd); block_tables: (B, MB);
    pos: (B,) first-query logical position.  Returns (B, S, H, hd).
    """
    B, S, H, hd = q.shape
    K = k_pool.shape[1]
    G = H // K
    kg = jnp.repeat(gather_kv(k_pool, block_tables), G, axis=2)
    vg = jnp.repeat(gather_kv(v_pool, block_tables), G, axis=2)
    hi = jax.lax.Precision.HIGHEST      # full f32 on TPU too (default: bf16)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   kg.astype(jnp.float32), precision=hi) * (hd ** -0.5)
    kv_pos = jnp.arange(kg.shape[1])
    q_pos = pos[:, None] + jnp.arange(S)[None, :]           # (B, S)
    mask = kv_pos[None, None, :] <= q_pos[:, :, None]       # (B, S, MB*bs)
    s = jnp.where(mask[:, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bhqd", p, vg.astype(jnp.float32),
                     precision=hi)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)
