"""Plain f32 reference of the dense block the program serves.

Written from the block's equations, importing nothing of the program:

  x = embed[tokens]
  per layer:  h = rms(x) * (1 + ln1);  q, k, v = h wq, h wk, h wv
              rotary on q, k (rotate-half, theta from the configuration)
              causal grouped-query attention (n_heads over n_kv_heads)
              x = x + attn wo
              h = rms(x) * (1 + ln2);  x = x + (silu(h wg) * (h wi)) wo
  logits = (rms(x) * (1 + final)) head        (head = embed^T when tied)

Weights come from ``bench.weights`` one layer at a time, so the
reference fits beside nothing else on the chip.  Matrix products run at
``highest`` precision.  ``precision="fp8"`` is the control: every matrix
product's operands are rounded to float8 e4m3 with one scale per tensor
(weights) or per row (activations), as an fp8 serving path would.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import make_layer_f32, make_top_f32

PAD = 256               # logits rows are padded to a multiple (fewer shapes)
Q_CHUNK = 512           # attention query rows per block
E4M3_MAX = 448.0


def _q8(x, axis):
    """Round to float8 e4m3 with an absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, fp8: bool):
    """a (..., n) @ w (n, m)."""
    if fp8:
        a, w = _q8(a, -1), _q8(w, None)
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, pos, theta):
    """x (T, h, hd): rotate-half rotary at positions ``pos`` (T,)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, seg, fp8: bool):
    """Causal GQA within each packed sequence; q (T,H,hd), k v (T,K,hd),
    ``seg`` (T,) the sequence each row belongs to (-1: padding)."""
    T, H, hd = q.shape
    G = H // k.shape[1]
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    if fp8:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
    kpos = jnp.arange(T)
    outs = []
    for s in range(0, T, Q_CHUNK):
        qc = q[s:s + Q_CHUNK]
        sc = jnp.einsum("qhd,khd->hqk", qc, k,
                        precision=jax.lax.Precision.HIGHEST) * hd ** -0.5
        qpos = s + jnp.arange(qc.shape[0])
        mask = ((kpos[None, :] <= qpos[:, None])
                & (seg[None, :] == seg[s:s + Q_CHUNK, None]))
        sc = jnp.where(mask[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if fp8:
            p = _q8(p, -1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v,
                               precision=jax.lax.Precision.HIGHEST))
    return jnp.concatenate(outs, 0)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _layer(x, w, seg, pos, spec, fp8):
    m = dict(spec)
    T = x.shape[0]
    H, K, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    h = _rms(x, w["ln1/scale"], m["norm_eps"])
    q = _mm(h, w["attn/wq"], fp8).reshape(T, H, hd)
    k = _mm(h, w["attn/wk"], fp8).reshape(T, K, hd)
    v = _mm(h, w["attn/wv"], fp8).reshape(T, K, hd)
    q, k = _rope(q, pos, m["rope_theta"]), _rope(k, pos, m["rope_theta"])
    a = _attention(q, k, v, seg, fp8).reshape(T, H * hd)
    x = x + _mm(a, w["attn/wo"], fp8)
    h = _rms(x, w["ln2/scale"], m["norm_eps"])
    g = _mm(h, w["mlp/wg"], fp8)
    return x + _mm(jax.nn.silu(g) * _mm(h, w["mlp/wi"], fp8),
                   w["mlp/wo"], fp8)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, rows, top, spec, fp8):
    m = dict(spec)
    h = _rms(x[rows], top["final_norm/scale"], m["norm_eps"])
    w = top["embed/tokens"].T if m["tie_embeddings"] else top["lm_head/w"]
    return _mm(h, w, fp8)


def logits_at(m: dict, seed: int, seqs: list, rows: list, pack: int,
              precision: str = "f32") -> list:
    """Reference logits of each sequence at the given positions.

    ``seqs``: 1-D int token arrays, packed side by side into one row of
    ``pack`` tokens (each attends only within itself), so every run
    compiles one shape; ``rows[i]``: positions of ``seqs[i]`` whose
    next-token logits are wanted.  Returns one (len(rows[i]), V) f32
    numpy array per sequence.
    """
    fp8 = precision == "fp8"
    spec = tuple(sorted(m.items()))
    ids = np.zeros(pack, np.int32)
    seg = np.full(pack, -1, np.int32)
    pos = np.zeros(pack, np.int32)
    want, at = [], 0
    for i, (s, r) in enumerate(zip(seqs, rows)):
        n = len(s)
        if at + n > pack:
            raise ValueError(f"sequences need more than {pack} tokens")
        ids[at:at + n], seg[at:at + n], pos[at:at + n] = s, i, np.arange(n)
        want.append(at + np.asarray(r))
        at += n
    flat = np.concatenate(want)
    padded = np.zeros(-(-len(flat) // PAD) * PAD, np.int32)
    padded[:len(flat)] = flat
    top = make_top_f32(m, seed)
    x = jnp.take(top["embed/tokens"], jnp.asarray(ids), axis=0)
    seg, pos = jnp.asarray(seg), jnp.asarray(pos)
    for layer in range(m["n_layers"]):
        x = _layer(x, make_layer_f32(m, seed, layer), seg, pos, spec, fp8)
    out = np.asarray(_head(x, jnp.asarray(padded), top, spec, fp8))
    return np.split(out[:len(flat)], np.cumsum([len(r) for r in rows])[:-1])
