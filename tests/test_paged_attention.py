"""Paged-attention parity: Pallas kernel (interpret mode) vs the jnp
oracle vs the pre-kernel gather path, across block sizes, tail-block
lengths, shared-prefix tables, int8-quantized KV content, and the
engine-level decode step (gather impl vs paged impl, every context
bucket)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.kernels.paged_attention import paged_attention, paged_attention_ref
from repro.kernels.quant import dequantize_ref, quantize_ref
from repro.models import lm
from repro.models.attention import decode_attention, paged_decode_attention
from repro.models.lm import ModelKnobs

RNG = np.random.default_rng(7)


def _rand(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape), jnp.float32).astype(dtype)


def _case(B, S, H, K, hd, bs, MB, NB=None, pos=None):
    NB = NB or (B * MB + 3)
    q = _rand((B, S, H, hd))
    kp = _rand((NB, K, bs, hd))          # head-major pool blocks
    vp = _rand((NB, K, bs, hd))
    bt = jnp.asarray(RNG.integers(0, NB, (B, MB)), jnp.int32)
    if pos is None:
        pos = RNG.integers(0, MB * bs - S, (B,))
    pos = jnp.asarray(pos, jnp.int32)
    return q, kp, vp, bt, pos


def _gather_path(q, kp, vp, bt, pos):
    """The pre-kernel serving path verbatim: dense gather + dense decode
    attention (models.lm paged branch with attn_impl="gather")."""
    B, S, H, hd = q.shape
    NB, K, bs, _ = kp.shape
    MB = bt.shape[1]
    kg = kp[bt].transpose(0, 1, 3, 2, 4).reshape(B, MB * bs, K, hd)
    vg = vp[bt].transpose(0, 1, 3, 2, 4).reshape(B, MB * bs, K, hd)
    return decode_attention(q, kg, vg, pos=pos)


@pytest.mark.parametrize("B,S,H,K,hd,bs,MB", [
    (2, 1, 4, 2, 16, 8, 6),      # single-token decode, GQA
    (4, 1, 4, 4, 32, 16, 4),     # MHA-style, bigger blocks
    (1, 1, 8, 2, 64, 8, 12),     # deep table
    (3, 5, 4, 2, 16, 8, 6),      # multi-token chunked decode
    (2, 7, 6, 2, 32, 16, 6),     # chunk not dividing block size
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_matches_ref(B, S, H, K, hd, bs, MB, dtype):
    q, kp, vp, bt, pos = _case(B, S, H, K, hd, bs, MB)
    q, kp, vp = q.astype(dtype), kp.astype(dtype), vp.astype(dtype)
    out = paged_attention(q, kp, vp, bt, pos, interpret=True)
    ref = paged_attention_ref(q, kp, vp, bt, pos)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_tail_block_lengths():
    """Every partial fill of the last live block is masked correctly —
    position sweeps across a block boundary (kernel, blocked path and
    gather path all agree with the oracle)."""
    B, S, H, K, hd, bs, MB = 1, 1, 4, 2, 16, 8, 4
    for p in list(range(0, 2 * bs + 1)) + [MB * bs - 2]:
        q, kp, vp, bt, pos = _case(B, S, H, K, hd, bs, MB, pos=[p])
        ref = paged_attention_ref(q, kp, vp, bt, pos)
        ker = paged_attention(q, kp, vp, bt, pos, interpret=True)
        blk = paged_decode_attention(q, kp, vp, bt, pos=pos)
        gat = _gather_path(q, kp, vp, bt, pos)
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5, err_msg=f"pos={p}")
        np.testing.assert_allclose(np.asarray(blk), np.asarray(ref),
                                   atol=5e-3, rtol=5e-3, err_msg=f"pos={p}")
        np.testing.assert_allclose(np.asarray(blk), np.asarray(gat),
                                   atol=1e-5, rtol=1e-5, err_msg=f"pos={p}")


def test_shared_prefix_tables():
    """Two requests whose tables alias the same physical prefix blocks
    (the pool's COW sharing) read identical prefix KV; a third private
    request is unaffected."""
    B, S, H, K, hd, bs, MB, NB = 3, 1, 4, 2, 16, 8, 4, 16
    q, kp, vp, _, _ = _case(B, S, H, K, hd, bs, MB, NB=NB)
    q = q.at[1].set(q[0])        # identical query for the sharing pair
    bt = np.array([[1, 2, 3, 0],
                   [1, 2, 4, 0],        # shares blocks 1, 2 with request 0
                   [5, 6, 7, 8]], np.int32)
    pos = jnp.asarray([15, 15, 15], jnp.int32)   # inside the shared blocks
    bt = jnp.asarray(bt)
    ref = paged_attention_ref(q, kp, vp, bt, pos)
    ker = paged_attention(q, kp, vp, bt, pos, interpret=True)
    blk = paged_decode_attention(q, kp, vp, bt, pos=pos)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(blk), np.asarray(ref),
                               atol=5e-3, rtol=5e-3)
    # requests 0 and 1 differ only through their (masked-out) third block
    np.testing.assert_allclose(np.asarray(ker[0]), np.asarray(ker[1]),
                               atol=1e-6, rtol=1e-6)


def test_int8_quantized_kv_layout():
    """The pool's int8 KV layout (blockwise fake-quant: values stored
    dequantized in pool dtype) flows through kernel and fallback
    unchanged — parity holds on quantized content."""
    B, S, H, K, hd, bs, MB = 2, 1, 4, 2, 16, 8, 6
    q, kp, vp, bt, pos = _case(B, S, H, K, hd, bs, MB)

    def fake_quant(x):
        flat = np.asarray(x, np.float32).reshape(-1)
        half = jnp.full(flat.shape, 0.5, jnp.float32)
        qv, sc = quantize_ref(jnp.asarray(flat), half, block=K * hd)
        return dequantize_ref(qv, sc, block=K * hd).reshape(x.shape)

    kp, vp = fake_quant(kp), fake_quant(vp)
    ref = paged_attention_ref(q, kp, vp, bt, pos)
    ker = paged_attention(q, kp, vp, bt, pos, interpret=True)
    blk = paged_decode_attention(q, kp, vp, bt, pos=pos)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(blk), np.asarray(ref),
                               atol=5e-3, rtol=5e-3)


@pytest.mark.parametrize("bs", [8, 16])
def test_decode_step_paged_matches_gather(bs):
    """Engine-level parity: the full decode step through the paged
    implementation equals the pre-kernel gather implementation for every
    context bucket that covers the batch, at both block sizes."""
    cfg = get_config("starcoder2-3b").reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    max_seq = 96
    n_slots, MB = 4, -(-max_seq // bs)
    nb = n_slots * MB + 1
    shapes = lm.init_paged_cache_shapes(cfg, nb, bs)
    cache = {k: _rand(s.shape) for k, s in shapes.items()}
    bt = np.arange(n_slots * MB).reshape(n_slots, MB) % (nb - 1) + 1
    cache["block_tables"] = jnp.asarray(bt, jnp.int32)
    tok = jnp.asarray(RNG.integers(0, cfg.vocab_size, (n_slots, 1)),
                      jnp.int32)
    pos = jnp.asarray([3, 17, 30, 9], jnp.int32)

    lg_g, nc_g = lm.decode_step(params, cache, tok, pos, cfg, None,
                                ModelKnobs(attn_impl="gather"))
    need = int(pos.max()) // bs + 1
    for cols in [0] + [c for c in range(need, MB + 1)]:
        lg_p, nc_p = lm.decode_step(
            params, cache, tok, pos, cfg, None,
            ModelKnobs(attn_impl="paged", attn_ctx=cols))
        np.testing.assert_allclose(np.asarray(lg_p, np.float32),
                                   np.asarray(lg_g, np.float32),
                                   atol=2e-2, rtol=2e-2,
                                   err_msg=f"cols={cols}")
        for k in ("k", "v"):    # cache writes are impl-independent
            np.testing.assert_array_equal(np.asarray(nc_p[k]),
                                          np.asarray(nc_g[k]))


def test_bucket_pad_writes_go_to_trash_block():
    """Chunked-decode positions past the block table (bucket padding in
    the engine's shared-prefix prefill) must land in physical block 0 —
    the pool's trash block — not clamp onto the last live column, where
    their (block, offset) rows would collide with real suffix KV."""
    cfg = get_config("starcoder2-3b").reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    bs, MB, nb = 8, 4, 9
    shapes = lm.init_paged_cache_shapes(cfg, nb, bs)
    cache = {k: _rand(s.shape) for k, s in shapes.items()}
    before = {k: np.asarray(v) for k, v in cache.items()}
    cache["block_tables"] = jnp.asarray([[5, 6, 7, 8]], jnp.int32)
    tok = jnp.asarray(RNG.integers(0, cfg.vocab_size, (1, 8)), jnp.int32)
    # queries at 28..35: 28..31 are real (column 3 = block 8, off 4..7);
    # 32..35 are past the 32-position table -> must hit the trash block
    pos = jnp.asarray([28], jnp.int32)
    _, nc = lm.decode_step(params, cache, tok, pos, cfg, None,
                           ModelKnobs(attn_impl="paged"))
    for key in ("k", "v"):
        after = np.asarray(nc[key])       # (L, NB, K, bs, hd)
        # real rows were written
        assert not np.allclose(after[:, 8, :, 4:], before[key][:, 8, :, 4:])
        # rows 0..3 of the last live block (logical 24..27) are untouched
        np.testing.assert_array_equal(after[:, 8, :, :4],
                                      before[key][:, 8, :, :4])
        # the pad rows went to the trash block
        assert not np.allclose(after[:, 0, :, :4], before[key][:, 0, :, :4])


def test_multi_token_chunked_decode_paged():
    """S>1 paged decode (the shared-prefix suffix prefill): causality
    inside the chunk matches the oracle token by token."""
    B, S, H, K, hd, bs, MB = 2, 6, 4, 2, 16, 8, 6
    q, kp, vp, bt, pos = _case(B, S, H, K, hd, bs, MB, pos=[11, 24])
    ref = paged_attention_ref(q, kp, vp, bt, pos)
    ker = paged_attention(q, kp, vp, bt, pos, interpret=True)
    blk = paged_decode_attention(q, kp, vp, bt, pos=pos)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(blk), np.asarray(ref),
                               atol=5e-3, rtol=5e-3)
    # each query row must equal a single-token call at its own position
    for j in range(S):
        one = paged_attention(q[:, j:j + 1], kp, vp, bt, pos + j,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(one[:, 0]),
                                   np.asarray(ker[:, j]),
                                   atol=2e-5, rtol=2e-5, err_msg=f"j={j}")
