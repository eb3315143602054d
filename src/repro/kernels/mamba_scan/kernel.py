"""Pallas TPU chunked selective-scan kernel (mamba1 recurrence).

The jnp reference scans one timestep at a time, reading and writing the
(B, D, N) state from HBM every step — that's what makes the falcon-mamba
train cell memory-bound in the roofline table. This kernel keeps the state
tile resident in VMEM across the whole sequence: grid = (B, n_d_blocks,
n_chunks) with the chunk axis sequential, and an (N, block_d) fp32 scratch
carrying h between chunk invocations. HBM traffic for the state drops from
O(S * D * N) to O(D * N) per (batch, block).

Layout note: the state is kept transposed (N, block_d) so the D axis lies on
TPU lanes (128-wide); N=16 sits on sublanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, hout_ref, h_ref,
                 *, chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...].astype(jnp.float32)            # (N, bd)  (transposed A)

    def body(t, h):
        dt_t = dt_ref[0, t].astype(jnp.float32)   # (bd,)
        x_t = x_ref[0, t].astype(jnp.float32)     # (bd,)
        b_t = b_ref[0, t].astype(jnp.float32)     # (N,)
        c_t = c_ref[0, t].astype(jnp.float32)     # (N,)
        dA = jnp.exp(dt_t[None, :] * a)           # (N, bd)
        h = dA * h + (dt_t * x_t)[None, :] * b_t[:, None]
        y_t = jnp.sum(h * c_t[:, None], axis=0)   # (bd,)
        y_ref[0, t] = y_t.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, body, h_ref[...])
    h_ref[...] = h

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        hout_ref[0] = h_ref[...].astype(hout_ref.dtype)


def selective_scan(x, dt, Bm, Cm, A, *, chunk: int = 64,
                   block_d: int = 128, interpret: bool = False):
    """x, dt: (B, S, D); Bm, Cm: (B, S, N); A: (D, N).

    Returns (y: (B, S, D) fp32, h_last: (B, D, N) fp32) — same contract as
    ref.selective_scan_ref.
    """
    B, S, D = x.shape
    N = A.shape[1]
    block_d = min(block_d, D)
    while D % block_d:
        block_d //= 2
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    nd, nc = D // block_d, S // chunk
    At = A.T                                       # (N, D)

    kernel = functools.partial(_scan_kernel, chunk=chunk, n_chunks=nc)

    y, h_t = pl.pallas_call(
        kernel,
        grid=(B, nd, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((N, block_d), lambda b, d, c: (0, d)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, N, block_d), lambda b, d, c: (b, 0, d)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, D), jnp.float32),
            jax.ShapeDtypeStruct((B, N, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, Bm, Cm, At)
    return y, h_t.transpose(0, 2, 1)               # (B, D, N)
