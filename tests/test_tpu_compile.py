"""Compile rehearsal: the paged-attention kernel compiled for a described
TPU v5e chip at starcoder2-3b's widths (24 query heads, 2 KV heads,
head_dim 128), for the decode (S=1) and spec_k=2 verify (S=3) widths,
both pool dtypes and both block sizes.  Nothing runs: the TPU compiler
refuses here what it would refuse on the chip (tile alignment, VMEM), at
no chip time.  The topology is described inside a fixture, never at
import, so every xdist worker collects the same tests and only the one
running this file loads the TPU compiler."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention import paged_attention_op

B, H, K, HD, MB = 8, 24, 2, 128, 64       # starcoder2-3b, max_seq 1024/16


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


@pytest.mark.parametrize("bs", [8, 16])
@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("S", [1, 3])
def test_paged_attention_compiles_for_v5e(one_chip, S, pool_dtype, bs):
    NB = B * MB + 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = paged_attention_op.lower(
        sds((B, S, H, HD), jnp.bfloat16),
        sds((NB, K, bs, HD), pool_dtype), sds((NB, K, bs, HD), pool_dtype),
        sds((B, MB), jnp.int32), sds((B,), jnp.int32),
        ctx_cols=MB // 2).compile()
    assert "tpu_custom_call" in compiled.as_text()
