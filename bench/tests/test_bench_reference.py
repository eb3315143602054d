"""The dense block's f32 reference against the program's forward pass
(reduced size), and the weights it rebuilds layer by layer against the
served tree."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from bench import blocks

SEED = 2 ** 33 + 7
dense = blocks.of(tiny.CONF)


def _program_logits(m, seed, toks):
    from repro.configs.registry import get_config
    from repro.models import lm
    cfg = dataclasses.replace(get_config("starcoder2-3b"), **m)
    params = dense.make_params(m, seed)
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
    ref = dense.logits_at(m, SEED, [toks], [rows], 64)[0]
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
    wrong = dense.logits_at(dict(m, rope_theta=10.0), SEED, [toks],
                            [rows], 64)[0]
    assert _rel(got, wrong) > 0.1


def test_control_is_further_off_than_the_program():
    m = dict(tiny.MODEL)
    toks = np.random.default_rng(2).integers(0, m["vocab_size"], 40)
    rows = np.arange(len(toks))
    ref = dense.logits_at(m, SEED, [toks], [rows], 64)[0]
    low = dense.logits_at(m, SEED, [toks], [rows], 64, precision="fp8")[0]
    got = _program_logits(m, SEED, toks)
    assert _rel(low, ref) > 3 * _rel(got, ref)


def test_packed_sequences_do_not_see_each_other():
    m = dict(tiny.MODEL)
    rng = np.random.default_rng(3)
    a, b = rng.integers(0, 256, 20), rng.integers(0, 256, 30)
    alone = dense.logits_at(m, SEED, [b], [np.arange(30)], 64)[0]
    both = dense.logits_at(m, SEED, [a, b],
                           [np.arange(20), np.arange(30)], 64)[1]
    np.testing.assert_allclose(both, alone, rtol=1e-5, atol=1e-5)


def test_a_layer_drawn_alone_equals_the_served_layer():
    m = dict(tiny.MODEL)
    p = dense.make_params(m, SEED)
    for layer in range(m["n_layers"]):
        alone = dense.make_layer_f32(m, SEED, layer)
        for name, v in alone.items():
            a, b = name.split("/")
            served = p["layers"][a][b][layer].astype(jnp.float32)
            np.testing.assert_array_equal(np.asarray(v), np.asarray(served))
    top = dense.make_top_f32(m, SEED)
    np.testing.assert_array_equal(
        np.asarray(top["embed/tokens"]),
        np.asarray(p["embed"]["tokens"].astype(jnp.float32)))
    assert all(v.dtype == jnp.bfloat16 for v in jax.tree.leaves(p))


def test_seeds_give_different_weights():
    m = dict(tiny.MODEL)
    a = dense.make_top_f32(m, SEED)["embed/tokens"]
    b = dense.make_top_f32(m, SEED + 2 ** 32)["embed/tokens"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))


# sha256 of each bf16 leaf at tiny.MODEL and SEED, as the served tree was
# drawn before the blocks were split out (``bench/weights.py``); the tied
# tree is the same without ``lm_head/w``
DIGESTS = {
    "embed/tokens":
        "6a7c524b304eb44e3c23d36fdb414302e5deca9c022f4e32fc435a94886ac0d8",
    "final_norm/scale":
        "124d1f88e8ec8e44c90f24b1eb7714361c74305c7a5b7f949bf27b77d30cc3f7",
    "layers/attn/wk":
        "c5f5ee1e2940a52a2584ca6ff71926c5146e3e34148f2deea5e679455925f243",
    "layers/attn/wo":
        "e1e7144edbd8d5d5a53ab741baaf96b1e0c2d3b256ae51cdd4dc96c489004ba0",
    "layers/attn/wq":
        "7b592c8597f0168db399a8b3b27bd071807f0c64db294dda042215c020141c75",
    "layers/attn/wv":
        "2a57ec53eff3f38784baea4f70102c7a9d4a9e4c93ea5d2d50060aa6b8f5030f",
    "layers/ln1/scale":
        "73ebe3f90fa31231e90363aeb5fc9a8da667be96029c9ad6da0956fc1785b8e2",
    "layers/ln2/scale":
        "3e1b358dc6e9db520ed3ba4471cba6f5db052318f670195ad0eb751a75f1b1ab",
    "layers/mlp/wg":
        "88ffa3f50b1d449b0ad0f4a7de60ea534d7ca675f16528bad2e09ba41af98d4d",
    "layers/mlp/wi":
        "73b8d834f0d7affde7a26587bd519a4788f1078ddaa1a6140d8931c97ba65257",
    "layers/mlp/wo":
        "27608f872a8772ecdba303e048a6aae8f1152c3103f37c6d782da2ce1d6871d1",
    "lm_head/w":
        "1dcddfa57523dea3125b99ec46542fac61cfe5ff80340c29a010f2bd03e9b24f",
}


@pytest.mark.parametrize("tied", [False, True])
def test_served_tree_is_pinned(tied):
    p = dense.make_params(dict(tiny.MODEL, tie_embeddings=tied), SEED)
    got = {"/".join(k.key for k in path):
           hashlib.sha256(np.asarray(v).tobytes()).hexdigest()
           for path, v in jax.tree_util.tree_flatten_with_path(p)[0]}
    want = {k: v for k, v in DIGESTS.items()
            if not (tied and k == "lm_head/w")}
    assert got == want
