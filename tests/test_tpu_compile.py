"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode never asks the TPU compiler about block tiling, scratch
shapes or lowerable primitives; these tests do, by compiling for a
described ``v5e:2x2`` topology (no chip needed, nothing runs). The
topology is described inside a fixture, never at import: only one process
at a time may hold the TPU library, and every xdist worker imports this
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.kernels.writhe import writhe_map

# (H, K, D): stablelm-1.6b (MHA, head_dim 64) and internlm2-1.8b (GQA 2:1,
# head_dim 128)
WIDTHS = [(32, 32, 64), (16, 8, 128)]
SLOTS, CACHE_LEN, PAGE = 8, 4096, 64


@pytest.fixture(scope="module")
def one_chip():
    """A sharding on chip 0 of a described v5e:2x2, with the persistent
    compilation cache off (entries compiled for a described chip cannot be
    read back without one)."""
    from jax.experimental import topologies

    old_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()
    if old_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = old_log_dir


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel, not XLA
    return compiled


def test_writhe_map_compiles(one_chip):
    coords = jax.ShapeDtypeStruct((8, 512, 3), jnp.float32, sharding=one_chip)
    _compile(writhe_map, coords)


@pytest.mark.parametrize("h,k,d", WIDTHS)
def test_flash_decode_compiles(one_chip, h, k, d):
    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    kv = spec((SLOTS, CACHE_LEN, k, d))
    _compile(flash_decode, spec((SLOTS, 1, h, d)), kv, kv,
             spec((SLOTS,), jnp.int32), spec((SLOTS, CACHE_LEN), jnp.int32))


@pytest.mark.parametrize("h,k,d", WIDTHS)
def test_flash_decode_paged_compiles(one_chip, h, k, d):
    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pages_per_slot = CACHE_LEN // PAGE
    pool = spec((SLOTS * pages_per_slot + 1, k, PAGE, d))
    _compile(flash_decode_paged, spec((SLOTS, 1, h, d)), pool, pool,
             spec((SLOTS,), jnp.int32),
             spec((SLOTS, pages_per_slot), jnp.int32))
