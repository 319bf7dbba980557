"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode never asks the TPU compiler about block tiling, scratch
shapes or lowerable primitives; these tests do, by compiling for a
described ``v5e:2x2`` topology (no chip needed, nothing runs). The
topology is described inside a fixture, never at import: only one process
at a time may hold the TPU library, and every xdist worker imports this
file.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import flash_decode as flash_decode_module
from repro.kernels.flash_decode import (flash_decode, flash_decode_latent,
                                        flash_decode_paged)
from repro.kernels.writhe import writhe_map
from repro.models import model_spec, param_shapes
from repro.models.transformer import init_paged_caches, paged_layout
from repro.serve.engine import jit_paged_step

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


@pytest.mark.parametrize("h,k,d", WIDTHS)
def test_flash_decode_paged_stacked_compiles(one_chip, h, k, d):
    """The kernel reads one layer of the stacked (L, P, K, page, D) pool in
    place, the layer index a scalar-prefetch operand."""
    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pages_per_slot = CACHE_LEN // PAGE
    pool = spec((3, SLOTS * pages_per_slot + 1, k, PAGE, d))
    _compile(flash_decode_paged, spec((SLOTS, 1, h, d)), pool, pool,
             spec((SLOTS,), jnp.int32),
             spec((SLOTS, pages_per_slot), jnp.int32), spec((), jnp.int32))


def test_flash_decode_latent_compiles(one_chip):
    """The latent kernel at Moonlight's widths: 16 query heads over one
    latent head, rows of 576 lanes padded to 640, values the first 512,
    read from a stacked pool at its layer."""
    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pages_per_slot = 1024 // PAGE
    pool = spec((6, 64 * pages_per_slot + 1, 1, PAGE, 640))
    compiled = _compile(
        lambda q, p, qp, t, l: flash_decode_latent(
            q, p, qp, t, l, dv=512, scale=1 / math.sqrt(192)),
        spec((64, 1, 16, 640)), pool, spec((64,), jnp.int32),
        spec((64, pages_per_slot), jnp.int32), spec((), jnp.int32))
    assert "flash_decode_latent" in compiled.as_text()


_HLO_OP = re.compile(r"^\s*(?:ROOT )?(%\S+) = \w+\[([\d,]*)\]\S* "
                     r"([\w-]+)\(([^)]*)\)")


def test_paged_serve_step_keeps_the_pool_in_place(one_chip, monkeypatch):
    """The paged serve step, jitted as the engine jits it (stablelm widths,
    12 slots x 1024, page 64; 2 layers keep the compile short, and any copy
    would be per layer), moves no pool: no copy, slice, scatter or fusion
    yields a layer's pool or the stack, each dynamic-update-slice into the
    stack writes one slot's row, and the donated pool is the output, all
    in the device's default layouts."""
    # trace the step as on the chip: the compiled paged kernel
    monkeypatch.setattr(flash_decode_module, "on_tpu", lambda: True)
    cfg = get_config("stablelm_1_6b").with_(
        n_layers=2, dtype="bfloat16", decode_kernel="flash")
    slots, max_len, page = 12, 1024, 64
    pages_per_slot, _ = paged_layout(max_len, page, slots)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip, param_shapes(model_spec(cfg),
                                                jnp.bfloat16))
    caches = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init_paged_caches(cfg, slots, max_len, jnp.bfloat16,
                                  page_size=page)))
    compiled = jit_paged_step(cfg).lower(
        params, on_chip(jax.ShapeDtypeStruct((slots, 1), jnp.int32)),
        caches, on_chip(jax.ShapeDtypeStruct((slots,), jnp.int32)),
        on_chip(jax.ShapeDtypeStruct((slots, pages_per_slot),
                                     jnp.int32))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the paged kernel, not XLA
    _assert_pool_in_place(compiled, caches["periods"]["0"]["pool_k"].shape,
                          caches)


def _assert_pool_in_place(compiled, stack, caches):
    """No copy, slice, scatter or fusion of the compiled step yields a
    layer's pool or the stack ``(L, P, K, page, D)``, each
    dynamic-update-slice into one writes one slot's row, the donated
    caches are aliased to the output, and the step's temporaries are
    smaller than one layer's pool."""
    pool_shapes = {stack, stack[1:]}
    row = stack[2] * stack[4]                  # one slot's (K, D) row
    ops = [m.groups() for m in map(_HLO_OP.match,
                                   compiled.as_text().splitlines()) if m]
    shape_of = {name: tuple(int(n) for n in dims.split(",") if n)
                for name, dims, _, _ in ops}
    moves = []
    for name, dims, op, operands in ops:
        if shape_of[name] not in pool_shapes:
            continue
        if op == "dynamic-update-slice" and math.prod(
                shape_of[operands.split(",")[1].strip()]) <= row:
            continue                           # one slot's row, in place
        if op in ("copy", "dynamic-slice", "dynamic-update-slice",
                  "scatter", "fusion"):
            moves.append((name, op, shape_of[name]))
    assert not moves, moves
    pool_bytes = sum(math.prod(a.shape) * a.dtype.itemsize
                     for a in jax.tree.leaves(caches))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < math.prod(stack[1:]) * 2  # < one layer


def test_paged_moonlight_step_keeps_the_pool_in_place(one_chip,
                                                      monkeypatch):
    """The whole paged Moonlight step at the cell's sizes (1 dense + 6
    expert layers at published widths, 64 slots x 1024, page 64), jitted
    as the engine jits it: the latent kernel runs in the dense layer and
    in the scanned expert layers, and the latent pools stay in place as
    stablelm's do."""
    monkeypatch.setattr(flash_decode_module, "on_tpu", lambda: True)
    cfg = get_config("moonlight_16b_a3b").with_(
        n_layers=7, dtype="bfloat16", decode_kernel="flash")
    slots, max_len, page = 64, 1024, 64
    pages_per_slot, _ = paged_layout(max_len, page, slots)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip, param_shapes(model_spec(cfg),
                                                jnp.bfloat16))
    caches = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init_paged_caches(cfg, slots, max_len, jnp.bfloat16,
                                  page_size=page)))
    compiled = jit_paged_step(cfg).lower(
        params, on_chip(jax.ShapeDtypeStruct((slots, 1), jnp.int32)),
        caches, on_chip(jax.ShapeDtypeStruct((slots,), jnp.int32)),
        on_chip(jax.ShapeDtypeStruct((slots, pages_per_slot),
                                     jnp.int32))).compile()
    text = compiled.as_text()
    # one call in the lead layer, one in the scan body
    assert len(re.findall(r"%flash_decode_latent\S* = ", text)) == 2
    stack = caches["periods"]["0"]["pool_latent"].shape
    assert stack == (6, slots * pages_per_slot + 1, 1, page, 640)
    assert caches["lead"]["0"]["pool_latent"].shape == stack[1:]
    _assert_pool_in_place(compiled, stack, caches)
