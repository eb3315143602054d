"""Random weights from the seed, made by the benchmark for both sides.

The program is handed the whole parameter tree in bf16, made on the
device in one jitted call.  The reference makes one layer at a time from
the same seed, and gets the same bf16 values (then widened to f32):
every leaf of layer ``l`` is drawn from its own key, ``fold_in(leaf, l)``,
so a layer drawn alone equals that layer of the stacked draw.

The tree has the layout the program's dense block takes (``models/lm.py``
``param_shapes``): embedding, stacked layers (two norms, attention
``wq wk wv wo``, SwiGLU ``wi wg wo``), final norm, and an output head
unless the embedding is tied.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NORM_STD = 0.1          # norm scales enter as (1 + scale)


def base_key(seed: int):
    """A PRNG key from any non-negative seed, 32 bits or more."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


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


def _leaf_id(name: str) -> int:
    return sum(ord(c) * 31 ** i for i, c in enumerate(name)) & 0x7FFFFFFF


def _draw(key, name, shape, std, dtype):
    k = jax.random.fold_in(key, _leaf_id(name))
    x = jax.random.normal(k, shape, jnp.float32) * std
    return x.astype(jnp.bfloat16).astype(dtype)


def _layer_draw(key, name, shape, std, layer, dtype):
    return _draw(jax.random.fold_in(key, layer), name, shape, std, dtype)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        *head, last = path.split("/")
        d = out
        for h in head:
            d = d.setdefault(h, {})
        d[last] = v
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def _make_params(key, spec):
    m = dict(spec)
    flat = {}
    layers = jnp.arange(m["n_layers"])
    for name, (shape, std) in layer_leaves(m).items():
        flat["layers/" + name] = jax.vmap(
            lambda l, name=name, shape=shape, std=std:
            _layer_draw(key, name, shape, std, l, jnp.bfloat16))(layers)
    for name, (shape, std) in top_leaves(m).items():
        flat[name] = _draw(key, name, shape, std, jnp.bfloat16)
    return _nest(flat)


def make_params(m: dict, seed: int):
    """The whole bf16 tree on the default device, in one jitted call."""
    return _make_params(base_key(seed), tuple(sorted(m.items())))


@functools.partial(jax.jit, static_argnums=(1,))
def _make_layer(key, spec, layer):
    m = dict(spec)
    return {name: _layer_draw(key, name, shape, std, layer, jnp.float32)
            for name, (shape, std) in layer_leaves(m).items()}


def make_layer_f32(m: dict, seed: int, layer: int) -> dict:
    """Layer ``layer``'s leaves as f32 (the bf16 values, widened)."""
    return _make_layer(base_key(seed), tuple(sorted(m.items())), layer)


@functools.partial(jax.jit, static_argnums=(1,))
def _make_top(key, spec):
    m = dict(spec)
    return {name: _draw(key, name, shape, std, jnp.float32)
            for name, (shape, std) in top_leaves(m).items()}


def make_top_f32(m: dict, seed: int) -> dict:
    """Embedding, final norm and head as f32 (the bf16 values, widened)."""
    return _make_top(base_key(seed), tuple(sorted(m.items())))
