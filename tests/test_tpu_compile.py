"""Compile the serving path's Pallas kernel for a TPU v5e that is
described, not attached (the TPU compiler is installed with jaxlib's
libtpu): what Mosaic refuses here would fail the first superstep on the
chip.  Nothing runs, so results are checked in tests/test_kernels.py.

The topology is described inside a module fixture, never at import: only
one process may load libtpu, and a per-worker import-time decision would
give pytest-xdist workers different test sets."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.extract_pack.kernel import extract_pack
from repro.kernels.extract_pack.ops import _fit_block


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# F = 3 * d_model: StarCoder2-7B (4608) in bf16, tide-tiny (128) in f32;
# T = gamma + 1
@pytest.mark.parametrize("f,dtype", [(13824, jnp.bfloat16),
                                     (384, jnp.float32)])
@pytest.mark.parametrize("t", [4, 8])
@pytest.mark.parametrize("b", [1, 8])
def test_extract_pack_compiles_for_v5e(one_chip, b, t, f, dtype):
    args = (jax.ShapeDtypeStruct((b, t, f), dtype, sharding=one_chip),
            jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((b, t), jnp.bool_, sharding=one_chip))
    fn = jax.jit(lambda x, y, m: extract_pack(
        x, y, m, block_f=_fit_block(f, 512), interpret=False))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
