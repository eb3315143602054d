"""Seeded drawing of random weights, shared by every block.

A block (``bench/blocks/<block>.py``) says which leaves it has, with
their shapes and scales, and draws them with these functions: the
program is handed the whole tree in bf16, made on the device in one
jitted call, and the reference draws one layer at a time from the same
seed and gets the same bf16 values (then widened to f32).  Every leaf of
layer ``l`` is drawn from its own key, ``fold_in(leaf, l)``, so a layer
drawn alone equals that layer of the stacked draw.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NORM_STD = 0.1          # norm scales enter as (1 + scale)


def base_key(seed: int):
    """A PRNG key from any non-negative seed, 32 bits or more."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def _leaf_id(name: str) -> int:
    return sum(ord(c) * 31 ** i for i, c in enumerate(name)) & 0x7FFFFFFF


def draw(key, name, shape, std, dtype):
    """The leaf ``name``: normal(0, std), rounded to bf16, as ``dtype``."""
    k = jax.random.fold_in(key, _leaf_id(name))
    x = jax.random.normal(k, shape, jnp.float32) * std
    return x.astype(jnp.bfloat16).astype(dtype)


def layer_draw(key, name, shape, std, layer, dtype):
    """Leaf ``name`` of layer ``layer`` (a traced index under ``vmap``)."""
    return draw(jax.random.fold_in(key, layer), name, shape, std, dtype)


def nest(flat: dict) -> dict:
    """``{"a/b": x}`` -> ``{"a": {"b": x}}``."""
    out: dict = {}
    for path, v in flat.items():
        *head, last = path.split("/")
        d = out
        for h in head:
            d = d.setdefault(h, {})
        d[last] = v
    return out
