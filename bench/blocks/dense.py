"""The dense block: the program's ``models/lm.py`` transformer layer.

Weights.  The tree has the layout the program's dense block takes
(``param_shapes``): embedding, stacked layers (two norms, attention
``wq wk wv wo``, SwiGLU ``wi wg wo``), final norm, and an output head
unless the embedding is tied.

Reference.  Written from the block's equations, importing nothing of
the program:

  x = embed[tokens]
  per layer:  h = rms(x) * (1 + ln1);  q, k, v = h wq, h wk, h wv
              rotary on q, k (rotate-half, theta from the configuration)
              causal grouped-query attention (n_heads over n_kv_heads)
              x = x + attn wo
              h = rms(x) * (1 + ln2);  x = x + (silu(h wg) * (h wi)) wo
  logits = (rms(x) * (1 + final)) head        (head = embed^T when tied)

Weights are drawn one layer at a time, so the reference fits beside
nothing else on the chip.

Counts.  Every layer has attention and the MLP; ``m`` is the
configuration file's ``model`` block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, weights
from bench.reference import mm, q8, rms
from bench.weights import NORM_STD

Q_CHUNK = 512           # attention query rows per block


# ------------------------------------------------------------- weights

def layer_leaves(m: dict) -> dict:
    """Per-layer leaf -> (shape without the layer axis, std)."""
    D, H, K, hd, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                      m["head_dim"], m["d_ff"])
    return {
        "ln1/scale": ((D,), NORM_STD),
        "ln2/scale": ((D,), NORM_STD),
        "attn/wq": ((D, H * hd), D ** -0.5),
        "attn/wk": ((D, K * hd), D ** -0.5),
        "attn/wv": ((D, K * hd), D ** -0.5),
        "attn/wo": ((H * hd, D), (H * hd) ** -0.5),
        "mlp/wi": ((D, F), D ** -0.5),
        "mlp/wg": ((D, F), D ** -0.5),
        "mlp/wo": ((F, D), F ** -0.5),
    }


def top_leaves(m: dict) -> dict:
    D, V = m["d_model"], m["vocab_size"]
    out = {"embed/tokens": ((V, D), D ** -0.5),
           "final_norm/scale": ((D,), NORM_STD)}
    if not m["tie_embeddings"]:
        out["lm_head/w"] = ((D, V), D ** -0.5)
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def _make_params(key, spec):
    m = dict(spec)
    flat = {}
    layers = jnp.arange(m["n_layers"])
    for name, (shape, std) in layer_leaves(m).items():
        flat["layers/" + name] = jax.vmap(
            lambda l, name=name, shape=shape, std=std:
            weights.layer_draw(key, name, shape, std, l, jnp.bfloat16))(
                layers)
    for name, (shape, std) in top_leaves(m).items():
        flat[name] = weights.draw(key, name, shape, std, jnp.bfloat16)
    return weights.nest(flat)


def make_params(m: dict, seed: int):
    """The whole bf16 tree on the default device, in one jitted call."""
    return _make_params(weights.base_key(seed), tuple(sorted(m.items())))


@functools.partial(jax.jit, static_argnums=(1,))
def _make_layer(key, spec, layer):
    m = dict(spec)
    return {name: weights.layer_draw(key, name, shape, std, layer,
                                     jnp.float32)
            for name, (shape, std) in layer_leaves(m).items()}


def make_layer_f32(m: dict, seed: int, layer: int) -> dict:
    """Layer ``layer``'s leaves as f32 (the bf16 values, widened)."""
    return _make_layer(weights.base_key(seed), tuple(sorted(m.items())),
                       layer)


@functools.partial(jax.jit, static_argnums=(1,))
def _make_top(key, spec):
    m = dict(spec)
    return {name: weights.draw(key, name, shape, std, jnp.float32)
            for name, (shape, std) in top_leaves(m).items()}


def make_top_f32(m: dict, seed: int) -> dict:
    """Embedding, final norm and head as f32 (the bf16 values, widened)."""
    return _make_top(weights.base_key(seed), tuple(sorted(m.items())))


# ----------------------------------------------------------- reference

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
        q, k, v = q8(q, -1), q8(k, -1), q8(v, -1)
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
            p = q8(p, -1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v,
                               precision=jax.lax.Precision.HIGHEST))
    return jnp.concatenate(outs, 0)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _layer(x, w, seg, pos, spec, fp8):
    m = dict(spec)
    T = x.shape[0]
    H, K, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    h = rms(x, w["ln1/scale"], m["norm_eps"])
    q = mm(h, w["attn/wq"], fp8).reshape(T, H, hd)
    k = mm(h, w["attn/wk"], fp8).reshape(T, K, hd)
    v = mm(h, w["attn/wv"], fp8).reshape(T, K, hd)
    q, k = _rope(q, pos, m["rope_theta"]), _rope(k, pos, m["rope_theta"])
    a = _attention(q, k, v, seg, fp8).reshape(T, H * hd)
    x = x + mm(a, w["attn/wo"], fp8)
    h = rms(x, w["ln2/scale"], m["norm_eps"])
    g = mm(h, w["mlp/wg"], fp8)
    return x + mm(jax.nn.silu(g) * mm(h, w["mlp/wi"], fp8),
                  w["mlp/wo"], fp8)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, rows, top, spec, fp8):
    m = dict(spec)
    h = rms(x[rows], top["final_norm/scale"], m["norm_eps"])
    w = top["embed/tokens"].T if m["tie_embeddings"] else top["lm_head/w"]
    return mm(h, w, fp8)


def logits_at(m: dict, seed: int, seqs: list, rows: list, pack: int,
              precision: str = "f32") -> list:
    """Reference logits of each sequence at the given positions.

    ``seqs``: 1-D int token arrays, packed side by side into one row of
    ``pack`` tokens (each attends only within itself); ``rows[i]``:
    positions of ``seqs[i]`` whose next-token logits are wanted.  Returns
    one (len(rows[i]), V) f32 numpy array per sequence.
    """
    fp8 = precision == "fp8"
    spec = tuple(sorted(m.items()))
    ids, seg, pos, want = reference.pack_rows(seqs, rows, pack)
    top = make_top_f32(m, seed)
    x = jnp.take(top["embed/tokens"], ids, axis=0)
    for layer in range(m["n_layers"]):
        x = _layer(x, make_layer_f32(m, seed, layer), seg, pos, spec, fp8)
    return reference.split_rows(_head(x, want, top, spec, fp8), rows)


# -------------------------------------------------------------- counts

def layer_params(m: dict) -> int:
    D, H, K, hd, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                      m["head_dim"], m["d_ff"])
    return D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F


def matmul_flops_per_token(m: dict) -> int:
    """Projections and MLP of every layer, for one token."""
    return 2 * m["n_layers"] * layer_params(m)


def head_flops(m: dict) -> int:
    """One row of logits."""
    return 2 * m["d_model"] * m["vocab_size"]


def attn_flops(m: dict, keys: int) -> int:
    """QK^T and PV of one query over ``keys`` keys, all layers."""
    return 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * keys


def prefill_flops(m: dict, n: int, start: int = 0) -> int:
    """``n`` prompt tokens after ``start`` cached ones, one logits row."""
    keys = n * start + n * (n + 1) // 2       # query i sees start + i keys
    return (n * matmul_flops_per_token(m) + attn_flops(m, keys)
            + head_flops(m))


def decode_flops(m: dict, keys: int) -> int:
    """One decoded token that attends ``keys`` keys (itself included)."""
    return matmul_flops_per_token(m) + head_flops(m) + attn_flops(m, keys)


def kv_bytes(m: dict, keys: int, itemsize: int = 2) -> int:
    """K and V of ``keys`` positions, all layers, read once per KV head."""
    return (2 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"] * keys
            * itemsize)


def paged_attention_call(m: dict, queries: int, start: int) -> tuple:
    """(flops, bytes) of one request's paged attention over all layers:
    ``queries`` query tokens at positions start .. start+queries-1."""
    keys = queries * start + queries * (queries + 1) // 2
    q_out = 2 * 2 * m["n_layers"] * m["n_heads"] * m["head_dim"] * queries
    return attn_flops(m, keys), kv_bytes(m, start + queries) + q_out
