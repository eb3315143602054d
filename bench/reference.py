"""What every block's f32 reference shares: packing and arithmetic.

A block's ``logits_at`` (``bench/blocks/<block>.py``) is given sequences
packed side by side into one row of ``pack`` tokens (``pack_rows``), so
every run compiles one shape; ``seg`` says which sequence each position
belongs to (-1: padding) and ``pos`` its position within it.  Matrix
products run at ``highest`` precision.  The control, ``precision="fp8"``,
rounds every matrix product's operands to float8 e4m3 with one scale per
tensor (weights) or per row (activations), as an fp8 serving path would.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PAD = 256               # logits rows are padded to a multiple (fewer shapes)
E4M3_MAX = 448.0


def q8(x, axis):
    """Round to float8 e4m3 with an absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(a, w, fp8: bool):
    """a (..., n) @ w (n, m)."""
    if fp8:
        a, w = q8(a, -1), q8(w, None)
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


def rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def pack_rows(seqs: list, rows: list, pack: int):
    """Pack ``seqs`` (1-D int token arrays) into one row of ``pack``
    tokens.  Returns ``ids``, ``seg``, ``pos`` (device arrays of
    ``pack``) and ``want``: the packed positions of every ``rows[i]``
    (positions of ``seqs[i]`` whose next-token logits are wanted), in
    order, padded to a multiple of ``PAD``."""
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
    return (jnp.asarray(ids), jnp.asarray(seg), jnp.asarray(pos),
            jnp.asarray(padded))


def split_rows(out, rows: list) -> list:
    """The logits of ``pack_rows``' ``want`` -> one (len(rows[i]), V) f32
    numpy array per sequence."""
    n = sum(len(r) for r in rows)
    return np.split(np.asarray(out)[:n],
                    np.cumsum([len(r) for r in rows])[:-1])
