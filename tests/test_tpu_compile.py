"""Compile rehearsal: the paged-attention kernel compiled for a described
TPU v5e chip at the benchmark cells' head geometries (24 query heads of
128 over 2 KV heads, starcoder2-3b; over 8, phi4-mini), for the decode
(S=1) and spec_k=2 verify (S=3) widths, both pool dtypes and both block
sizes, and a suffix prefill wide enough for two query tiles; and one
engine decode step at starcoder2-3b's widths (one layer).  Nothing runs:
the TPU compiler refuses here what it would refuse on the chip (tile
alignment, VMEM), at no chip time; and the compiled text shows the names
a profiler trace will carry.  The
topology is described inside a fixture, never at import, so every xdist
worker collects the same tests and only the one running this file loads
the TPU compiler."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.paged_attention import paged_attention_op
from repro.models import lm
from repro.models.lm import ModelKnobs
from repro.serving.engine import decode_fn

B, H, HD, MB = 8, 24, 128, 64      # 24 query heads of 128; max_seq 1024/16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_cases():
    """starcoder2-3b's 24/2 heads (ids as before) and phi4-mini's 24/8, at
    the decode and spec_k=2 verify widths, both pool dtypes and block
    sizes; and a suffix prefill whose S*G rows span two query tiles."""
    for K, tag in ((2, ""), (8, "phi4-")):
        for S in (1, 3):
            for dt in (jnp.bfloat16, jnp.float32):
                for bs in (8, 16):
                    yield pytest.param(S, K, dt, bs,
                                       id=f"{tag}{S}-{dt.__name__}-{bs}")
    for K, S in ((2, 64), (8, 256)):
        yield pytest.param(S, K, jnp.bfloat16, 16, id=f"K{K}-multi-{S}")


@pytest.mark.parametrize("S,K,pool_dtype,bs", _compile_cases())
def test_paged_attention_compiles_for_v5e(one_chip, S, K, pool_dtype, bs):
    NB = B * MB + 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(paged_attention_op, static_argnames="ctx_cols").lower(
        sds((B, S, H, HD), jnp.bfloat16),
        sds((NB, K, bs, HD), pool_dtype), sds((NB, K, bs, HD), pool_dtype),
        sds((B, MB), jnp.int32), sds((B,), jnp.int32),
        ctx_cols=MB // 2).compile().as_text()
    # each query width compiles through its own wrapper, which names the
    # custom call: a trace tells the forms apart by name, not by a number
    # the compiler assigns
    form = "decode" if S == 1 else "multi"
    calls = [ln.split(" = ", 1)[0].strip() for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls and all(c.startswith(f"%paged_attention_op_{form}.")
                         for c in calls), calls


def test_engine_decode_step_module_named(one_chip, monkeypatch):
    """An engine decode step at starcoder2-3b's widths (one layer) compiles
    for the chip as module ``jit_serve_decode``, its kernel named for the
    decode form.  The model takes its kernel branch only on a TPU backend,
    so the test steers that one check."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(get_config("starcoder2-3b"), n_layers=1)
    bs = 16
    NB = B * MB + 1

    def put(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(put, jax.eval_shape(
        lambda: lm.init_params(cfg, jax.random.PRNGKey(0))))
    cache = {k: put(v) for k, v in
             lm.init_paged_cache_shapes(cfg, NB, bs).items()}
    cache["block_tables"] = put(jax.ShapeDtypeStruct((B, MB), jnp.int32))
    tok = put(jax.ShapeDtypeStruct((B, 1), jnp.int32))
    pos = put(jax.ShapeDtypeStruct((B,), jnp.int32))
    fn = decode_fn(cfg, None, ModelKnobs(attn_ctx=MB // 2))
    text = jax.jit(fn).lower(params, cache, tok, pos).compile().as_text()
    assert text.startswith("HloModule jit_serve_decode,")
    assert "%paged_attention_op_decode." in text
