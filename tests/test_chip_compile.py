"""Compile-only checks for a described TPU v5e: the serving path's Pallas
kernel at published widths goes through the chip's own compiler here, with
no chip attached. Nothing runs, so these say nothing about results or
times; they catch what interpret mode cannot (tiling, VMEM limits, kernels
the compiler refuses).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and with
several test workers every worker must still collect the same tests.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention.kernel import paged_attention_pallas

# batch, pool pages, page size and table width of a 16-slot pool
B, N_PAGES, PAGE, MAX_PAGES = 16, 2049, 16, 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to JAX's persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("n_kv,n_q,hd,window", [
    (8, 24, 128, 0),        # llama3.2-3b
    (32, 32, 80, 0),        # stablelm-3b
    (8, 24, 128, 256),      # windowed
], ids=["llama3.2-3b", "stablelm-3b", "window"])
def test_paged_attention_compiles_for_v5e(one_chip, n_kv, n_q, hd, window):
    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    fn = functools.partial(paged_attention_pallas, window=window)
    compiled = jax.jit(fn).lower(
        shape(B, n_q, hd),
        shape(n_kv, N_PAGES, PAGE, hd), shape(n_kv, N_PAGES, PAGE, hd),
        shape(B, MAX_PAGES, dtype=jnp.int32),
        shape(B, dtype=jnp.int32)).compile()
    print(compiled.memory_analysis())
    assert "tpu_custom_call" in compiled.as_text()
