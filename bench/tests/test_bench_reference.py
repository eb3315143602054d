"""The f32 reference against the program's forward pass (reduced size),
and the weights it rebuilds layer by layer against the served tree."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from bench import reference, weights

SEED = 2 ** 33 + 7


def _program_logits(m, seed, toks):
    from repro.configs.registry import get_config
    from repro.models import lm
    cfg = dataclasses.replace(get_config("starcoder2-3b"), **m)
    params = weights.make_params(m, seed)
    hidden, _, _ = lm.forward(params, {"tokens": jnp.asarray(toks)[None]},
                              cfg, mode="prefill")
    return np.asarray(lm.logits_fn(params, hidden, cfg)[0], np.float32)


def _rel(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("tied", [False, True])
def test_reference_matches_the_program_forward(tied):
    m = dict(tiny.MODEL, tie_embeddings=tied)
    toks = np.random.default_rng(0).integers(0, m["vocab_size"], 40)
    got = _program_logits(m, SEED, toks)
    rows = np.arange(len(toks))
    ref = reference.logits_at(m, SEED, [toks], [rows], 64)[0]
    # the program runs bf16 activations and weights (the weights are the
    # same bf16 values the reference widens): rounding of 2^-9 per op over
    # two layers stays near 1e-2 of the logits' norm; a wrong rotary,
    # head grouping or norm is off by O(1) (see the test below)
    assert _rel(got, ref) < 0.03
    assert np.mean(got.argmax(-1) == ref.argmax(-1)) > 0.9


def test_a_wrong_block_is_far_off():
    m = dict(tiny.MODEL)
    toks = np.random.default_rng(1).integers(0, m["vocab_size"], 40)
    got = _program_logits(m, SEED, toks)
    rows = np.arange(len(toks))
    wrong = reference.logits_at(dict(m, rope_theta=10.0), SEED, [toks],
                                [rows], 64)[0]
    assert _rel(got, wrong) > 0.1


def test_control_is_further_off_than_the_program():
    m = dict(tiny.MODEL)
    toks = np.random.default_rng(2).integers(0, m["vocab_size"], 40)
    rows = np.arange(len(toks))
    ref = reference.logits_at(m, SEED, [toks], [rows], 64)[0]
    low = reference.logits_at(m, SEED, [toks], [rows], 64,
                              precision="fp8")[0]
    got = _program_logits(m, SEED, toks)
    assert _rel(low, ref) > 3 * _rel(got, ref)


def test_packed_sequences_do_not_see_each_other():
    m = dict(tiny.MODEL)
    rng = np.random.default_rng(3)
    a, b = rng.integers(0, 256, 20), rng.integers(0, 256, 30)
    alone = reference.logits_at(m, SEED, [b], [np.arange(30)], 64)[0]
    both = reference.logits_at(m, SEED, [a, b],
                               [np.arange(20), np.arange(30)], 64)[1]
    np.testing.assert_allclose(both, alone, rtol=1e-5, atol=1e-5)


def test_a_layer_drawn_alone_equals_the_served_layer():
    m = dict(tiny.MODEL)
    p = weights.make_params(m, SEED)
    for layer in range(m["n_layers"]):
        alone = weights.make_layer_f32(m, SEED, layer)
        for name, v in alone.items():
            a, b = name.split("/")
            served = p["layers"][a][b][layer].astype(jnp.float32)
            np.testing.assert_array_equal(np.asarray(v), np.asarray(served))
    top = weights.make_top_f32(m, SEED)
    np.testing.assert_array_equal(
        np.asarray(top["embed/tokens"]),
        np.asarray(p["embed"]["tokens"].astype(jnp.float32)))
    assert p["layers"]["attn"]["wq"].dtype == jnp.bfloat16


def test_seeds_give_different_weights():
    m = dict(tiny.MODEL)
    a = weights.make_top_f32(m, SEED)["embed/tokens"]
    b = weights.make_top_f32(m, SEED + 2 ** 32)["embed/tokens"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
