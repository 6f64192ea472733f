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
# temporaries of the kernel this schedule replaced, compiled as
# test_paged_attention_compiles_for_v5e[stablelm-3b] compiles: both pools
# re-laid to 128 lanes for hd 80 (2 x 32 x 2049 x 16 x 128 bf16) and q
STABLELM_SEED_TEMP_BYTES = 537_197_568


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


def _compile(sharding, b, n_pages, max_pages, n_kv, n_q, hd, window=0):
    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    fn = functools.partial(paged_attention_pallas, window=window)
    compiled = jax.jit(fn).lower(
        shape(b, n_q, hd),
        shape(n_kv, n_pages, PAGE, hd), shape(n_kv, n_pages, PAGE, hd),
        shape(b, max_pages, dtype=jnp.int32),
        shape(b, dtype=jnp.int32)).compile()
    print(compiled.memory_analysis())
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("b,n_pages,max_pages,n_kv,n_q,hd,window", [
    (B, N_PAGES, MAX_PAGES, 8, 24, 128, 0),       # llama3.2-3b
    (B, N_PAGES, MAX_PAGES, 32, 32, 80, 0),       # stablelm-3b
    (B, N_PAGES, MAX_PAGES, 8, 24, 128, 256),     # windowed
    (B, N_PAGES, MAX_PAGES, 8, 64, 128, 0),       # deepseek-67b stage
    (32, 4096, 161, 8, 64, 128, 0),               # deepseek67b.chat's pool
    (4, 640, 193, 32, 32, 80, 0),                 # stablelm3b.longdoc's pool
], ids=["llama3.2-3b", "stablelm-3b", "window", "deepseek-67b",
        "deepseek67b.chat", "stablelm3b.longdoc"])
def test_paged_attention_compiles_for_v5e(one_chip, b, n_pages, max_pages,
                                          n_kv, n_q, hd, window):
    _compile(one_chip, b, n_pages, max_pages, n_kv, n_q, hd, window)


def test_paged_attention_adds_no_temporaries_at_stablelm_widths(one_chip):
    """The stablelm-3b cell sits at the compiler's memory limit: the
    kernel may hold no more HBM temporaries than the one it replaced."""
    compiled = _compile(one_chip, B, N_PAGES, MAX_PAGES, 32, 32, 80)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= STABLELM_SEED_TEMP_BYTES, temp
