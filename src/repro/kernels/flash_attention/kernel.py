"""Pallas TPU flash-attention kernel (forward).

Grid: (B*H, n_q_blocks, n_kv_blocks); the kv dimension is the innermost
sequential ("arbitrary") axis, so the online-softmax state (m, l, acc) lives
in VMEM scratch across kv iterations. Causal blocks that are entirely in the
future are *skipped* via pl.when — unlike the jnp fallback, no masked-half
FLOPs are spent (this is the kernel-level fix for the roofline useful_ratio).

GQA is handled in the K/V index maps: query head h reads kv head h // G, so
the kv tensors are never materialized at H heads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, sm_scale, causal, block_q,
                  block_k, n_kb):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qp = qpos_ref[0]                                   # (block_q,)
    kp = kpos_ref[0]                                   # (block_k,)

    def _body():
        q = q_ref[0].astype(jnp.float32)               # (bq, hd)
        k = k_ref[0].astype(jnp.float32)               # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                               # (bq, bk)
        if causal:
            mask = qp[:, None] >= kp[None, :]
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, :1]                          # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)                 # (bq, 1)
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # skip blocks that are entirely in the future of every query position
        any_valid = jnp.max(qp) >= jnp.min(kp)
        pl.when(any_valid)(_body)
    else:
        _body()

    @pl.when(ki == n_kb - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q, k, v, q_positions=None, kv_positions=None, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: (B, Sq, H, hd); k, v: (B, Skv, K, hd) with H % K == 0.

    Returns (B, Sq, H, hd). ``*_positions``: (S,) absolute positions used for
    the causal mask (defaults: aligned suffix, i.e. q at Skv-Sq..Skv-1).
    """
    B, Sq, H, hd = q.shape
    Skv, K = k.shape[1], k.shape[2]
    G = H // K
    block_q = min(block_q, Sq)
    while Sq % block_q:
        block_q //= 2
    block_k = min(block_k, Skv)
    while Skv % block_k:
        block_k //= 2
    nq, nk = Sq // block_q, Skv // block_k

    if q_positions is None:
        q_positions = jnp.arange(Sq, dtype=jnp.int32) + (Skv - Sq)
    if kv_positions is None:
        kv_positions = jnp.arange(Skv, dtype=jnp.int32)
    qpos = q_positions.reshape(nq, block_q).astype(jnp.int32)
    kpos = kv_positions.reshape(nk, block_k).astype(jnp.int32)

    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * K, Skv, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * K, Skv, hd)

    def kv_index(bh, qi, ki):
        b = bh // H
        h = bh % H
        return (b * K + h // G, ki, 0)

    kernel = functools.partial(
        _flash_kernel, sm_scale=hd ** -0.5, causal=causal,
        block_q=block_q, block_k=block_k, n_kb=nk)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q), lambda bh, qi, ki: (qi, 0)),
            pl.BlockSpec((1, block_k), lambda bh, qi, ki: (ki, 0)),
            pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, hd), kv_index),
            pl.BlockSpec((1, block_k, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # m
            pltpu.VMEM((block_q, 128), jnp.float32),   # l
            pltpu.VMEM((block_q, hd), jnp.float32),    # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qpos, kpos, qf, kf, vf)
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
