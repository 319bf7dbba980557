"""Pallas TPU flash-decode: fused single-token attention for serving.

Decode is one query token per slot against a long KV cache — the serving
hot path. The kernel follows the split-KV flash-decode idiom: the grid is
``(batch, kv_head, kv_blocks)`` with the kv dimension sequential, and the
online-softmax running stats (m, l, acc) live in VMEM scratch across kv
steps exactly like ``flash_attention.py``. Because the grid is already per
KV head, the whole GQA group of query heads rides in one ``(G, D)`` block
and the group/KV matmul needs no repeat/broadcast.

Ragged continuous batching is expressed through two position inputs:

* ``q_positions`` (B,) — each slot's absolute decode position (scalar
  prefetch, read from SMEM);
* ``k_positions`` (B, S) — the absolute position held by each cache slot,
  with **-1 meaning invalid**. This one encoding covers dense prefixes
  (``arange`` masked at ``end``), ring buffers (slot ``j`` holds position
  ``t-1-((t-1-j) mod window)``; negatives = not yet written), padded slots
  and empty lanes, so the kernel needs no layout-specific masking.

Fully-masked kv blocks are skipped via ``pl.when`` around the body, so a
slot at position p does O(ceil(p/block_k)) work, not O(S_cache).

Three callables share the contract:

* :func:`flash_decode` — the Pallas kernel (TPU, or ``interpret=True``);
* :func:`flash_decode_xla` — the same split-KV online-softmax algorithm
  lowered through XLA with a *dynamic* trip count bounded by the furthest
  live position (``bounded=True``), the portable fast path on CPU/GPU;
* :func:`decode_attention` — backend dispatch between the two.

:func:`flash_decode_paged` / :func:`decode_attention_paged` are the paged
variants: KV lives in a physical page pool ``(P, K, page_size, D)`` and a
per-slot page table ``(B, pages_per_slot)`` (-1 = unbound) is scalar-
prefetched so the k/v ``index_map`` gathers pages directly — no logical
cache is ever materialized, and work scales with *bound pages*, not
``max_len``.

Every block's last two dims are whole array dims or (8, 128)-aligned, and
the online-softmax stats are 2-D ``(G, 1)`` VMEM scratch, so both kernels
compile for the TPU (``tests/test_tpu_compile.py`` checks this against a
described v5e). On TPU the dispatchers always run them compiled.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import on_tpu

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# online-softmax pieces shared by the dense and paged kernels
# ---------------------------------------------------------------------------


def _init_stats(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _attend_block(q, k, v, mask, m_scr, l_scr, acc_scr, scale):
    """Fold one kv block into the running (m, l, acc). q: (G, Dk);
    k: (bk, Dk); v: (bk, Dv); mask: (1, bk), at least one entry live."""
    q = q.astype(jnp.float32)
    k = k.astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)                  # (G, bk)
    m_prev = m_scr[...]                               # (G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
        p, v.astype(jnp.float32), preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def _finalize(o_ref, l_scr, acc_scr):
    # l == 0 (fully-masked slot, e.g. an empty lane) yields zeros, the
    # same convention as the chunked reference.
    o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-37)
                   ).astype(o_ref.dtype)


def _stat_scratch(g: int, dv: int) -> list:
    return [pltpu.VMEM((g, 1), jnp.float32),      # running max
            pltpu.VMEM((g, 1), jnp.float32),      # running sum
            pltpu.VMEM((g, dv), jnp.float32)]     # output accumulator


# ---------------------------------------------------------------------------
# dense (contiguous cache) kernel
# ---------------------------------------------------------------------------


def _decode_kernel(qpos_ref, q_ref, k_ref, v_ref, kpos_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float,
                   window: int | None, n_k: int):
    bi = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        _init_stats(m_scr, l_scr, acc_scr)

    qp = qpos_ref[bi]
    kp = kpos_ref[0]                         # (1, block_k) int32
    mask = (kp >= 0) & (kp <= qp)
    if window is not None:
        mask &= kp > qp - window

    @pl.when(jnp.any(mask))
    def _body():
        _attend_block(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], mask,
                      m_scr, l_scr, acc_scr, scale)

    @pl.when(ki == n_k - 1)
    def _out():
        _finalize(o_ref, l_scr, acc_scr)


@functools.partial(jax.jit, static_argnames=("window", "block_k", "scale",
                                             "interpret"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 q_positions: jax.Array,
                 k_positions: jax.Array | None = None, *,
                 window: int | None = None, block_k: int = 128,
                 scale: float | None = None,
                 interpret: bool = False) -> jax.Array:
    """q: (B, 1, H, Dk); k: (B, S, K, Dk); v: (B, S, K, Dv) -> (B, 1, H, Dv).

    ``q_positions``: (B,) int32 absolute position of each slot's query.
    ``k_positions``: (B, S) int32 cache-slot positions, -1 = invalid;
    defaults to ``arange(S)`` (contiguous prefix cache).
    """
    b, sq, h, dk = q.shape
    assert sq == 1, "flash_decode is single-token-per-slot"
    _, s, kh, _ = k.shape
    dv = v.shape[-1]
    g = h // kh
    if scale is None:
        scale = 1.0 / math.sqrt(dk)
    if k_positions is None:
        k_positions = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
    block_k = min(block_k, s)
    pad = (-s) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, ((0, 0), (0, pad)),
                              constant_values=-1)
    n_k = (s + pad) // block_k

    qt = q[:, 0].reshape(b, kh, g, dk)           # head h = kh*g + g_idx
    kt = k.transpose(0, 2, 1, 3)                 # (B, K, S, Dk)
    vt = v.transpose(0, 2, 1, 3)                 # (B, K, S, Dv)

    kernel = functools.partial(_decode_kernel, scale=scale, window=window,
                               n_k=n_k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kh, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, g, dk), lambda bi, hi, ki, qp: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, block_k, dk),
                         lambda bi, hi, ki, qp: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dv),
                         lambda bi, hi, ki, qp: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bi, hi, ki, qp: (bi, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dv),
                               lambda bi, hi, ki, qp: (bi, hi, 0, 0)),
        scratch_shapes=_stat_scratch(g, dv),
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, dv), q.dtype),
        interpret=interpret,
    )(q_positions.astype(jnp.int32), qt, kt, vt,
      k_positions.astype(jnp.int32)[:, None, :])
    return out.reshape(b, 1, h, dv)


@functools.partial(jax.jit, static_argnames=("window", "block_k", "scale",
                                             "bounded"))
def flash_decode_xla(q: jax.Array, k: jax.Array, v: jax.Array,
                     q_positions: jax.Array,
                     k_positions: jax.Array | None = None, *,
                     window: int | None = None, block_k: int = 128,
                     scale: float | None = None,
                     bounded: bool = True) -> jax.Array:
    """Same contract as :func:`flash_decode`, lowered through XLA.

    ``bounded=True`` (valid only when cache slot index == position, i.e.
    non-ring caches) runs the kv-block loop with a *dynamic* trip count
    ``ceil((max(q_positions)+1)/block_k)`` — per-step work scales with
    occupancy instead of cache capacity, which is where the long-context
    decode speedup over the full-cache chunked path comes from.
    """
    b, sq, h, dk = q.shape
    assert sq == 1
    _, s, kh, _ = k.shape
    dv = v.shape[-1]
    g = h // kh
    if scale is None:
        scale = 1.0 / math.sqrt(dk)
    if k_positions is None:
        k_positions = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
    block_k = min(block_k, s)
    pad = (-s) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, ((0, 0), (0, pad)),
                              constant_values=-1)
    n_k = (s + pad) // block_k
    qp = q_positions.astype(jnp.int32)
    qh = q[:, 0].reshape(b, kh, g, dk).astype(jnp.float32)

    if bounded:
        n_live = jnp.clip((jnp.max(qp) + block_k) // block_k, 0, n_k)
    else:
        n_live = jnp.asarray(n_k, jnp.int32)

    def body(i, carry):
        m_run, l_run, acc = carry
        kc = jax.lax.dynamic_slice_in_dim(k, i * block_k, block_k, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, i * block_k, block_k, axis=1)
        kp = jax.lax.dynamic_slice_in_dim(k_positions, i * block_k, block_k,
                                          axis=1)
        sc = jnp.einsum("bkgd,bckd->bkgc", qh, kc.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * scale
        mask = (kp >= 0) & (kp <= qp[:, None])
        if window is not None:
            mask &= kp > qp[:, None] - window
        sc = jnp.where(mask[:, None, None, :], sc, NEG_INF)
        m_new = jnp.maximum(m_run, sc.max(axis=-1))
        # mask p explicitly: in an all-invalid block m_new stays NEG_INF and
        # exp(NEG_INF - NEG_INF) = 1 would attend uniformly to garbage
        p = jnp.where(mask[:, None, None, :],
                      jnp.exp(sc - m_new[..., None]), 0.0)
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + p.sum(axis=-1)
        pv = jnp.einsum("bkgc,bckd->bkgd", p, vc.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        return m_new, l_new, acc * corr[..., None] + pv

    m0 = jnp.full((b, kh, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kh, g), jnp.float32)
    a0 = jnp.zeros((b, kh, g, dv), jnp.float32)
    _, l_f, acc = jax.lax.fori_loop(0, n_live, body, (m0, l0, a0))
    out = acc / jnp.maximum(l_f[..., None], 1e-37)
    return out.reshape(b, 1, h, dv).astype(q.dtype)


def decode_attention(q, k, v, q_positions, k_positions=None, *,
                     window=None, block_k=128, interpret=False,
                     bounded=True):
    """Backend dispatch: the compiled Pallas kernel on TPU, whatever
    ``interpret`` says; elsewhere the kernel in interpret mode when a test
    asks for it, and otherwise the split-KV XLA lowering."""
    if on_tpu() or interpret:
        return flash_decode(q, k, v, q_positions, k_positions,
                            window=window, block_k=block_k,
                            interpret=not on_tpu())
    return flash_decode_xla(q, k, v, q_positions, k_positions,
                            window=window, block_k=block_k, bounded=bounded)


# ---------------------------------------------------------------------------
# paged cache kernel
# ---------------------------------------------------------------------------


def _paged_kernel(qpos_ref, table_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float,
                  window: int | None, page_size: int, n_pages: int):
    bi = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        _init_stats(m_scr, l_scr, acc_scr)

    qp = qpos_ref[bi]
    page = table_ref[bi, pi]
    # pages are bound in logical order, so slot offsets map to positions
    # pi*page_size + offset directly; no per-slot position array needed.
    first = pi * page_size
    kp = first + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
    mask = kp <= qp
    live = (page >= 0) & (first <= qp)
    if window is not None:
        mask &= kp > qp - window
        live &= first + page_size - 1 > qp - window

    @pl.when(live)
    def _body():
        _attend_block(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], mask,
                      m_scr, l_scr, acc_scr, scale)

    @pl.when(pi == n_pages - 1)
    def _out():
        _finalize(o_ref, l_scr, acc_scr)


@functools.partial(jax.jit, static_argnames=("window", "scale", "interpret"))
def flash_decode_paged(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                       q_positions: jax.Array, page_table: jax.Array, *,
                       window: int | None = None, scale: float | None = None,
                       interpret: bool = False) -> jax.Array:
    """q: (B, 1, H, Dk); pool_k: (P, K, page_size, Dk); pool_v likewise with
    Dv; page_table: (B, pages_per_slot) int32, -1 = unbound (page 0 is the
    allocator's reserved trash page). -> (B, 1, H, Dv).

    The page table is scalar-prefetched so the k/v ``index_map`` gathers the
    physical page per grid step — unbound entries clamp to page 0 and are
    masked out by ``page >= 0`` inside the kernel.
    """
    b, sq, h, dk = q.shape
    assert sq == 1
    _, kh, page_size, _ = pool_k.shape
    dv = pool_v.shape[-1]
    g = h // kh
    n_pages = page_table.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(dk)
    qt = q[:, 0].reshape(b, kh, g, dk)

    kernel = functools.partial(_paged_kernel, scale=scale, window=window,
                               page_size=page_size, n_pages=n_pages)

    def kv_map(bi, hi, pi, qp, table):
        return (jnp.maximum(table[bi, pi], 0), hi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kh, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, g, dk),
                         lambda bi, hi, pi, qp, tb: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, page_size, dk), kv_map),
            pl.BlockSpec((1, 1, page_size, dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dv),
                               lambda bi, hi, pi, qp, tb: (bi, hi, 0, 0)),
        scratch_shapes=_stat_scratch(g, dv),
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, dv), q.dtype),
        interpret=interpret,
    )(q_positions.astype(jnp.int32), page_table.astype(jnp.int32),
      qt, pool_k, pool_v)
    return out.reshape(b, 1, h, dv)


@functools.partial(jax.jit, static_argnames=("window", "scale", "bounded"))
def flash_decode_paged_xla(q: jax.Array, pool_k: jax.Array,
                           pool_v: jax.Array, q_positions: jax.Array,
                           page_table: jax.Array, *,
                           window: int | None = None,
                           scale: float | None = None,
                           bounded: bool = True) -> jax.Array:
    """Paged decode through XLA: a dynamic-trip-count loop over page blocks,
    gathering one physical page per slot per step. Work scales with bound
    pages (occupancy), never materializing the logical cache."""
    b, sq, h, dk = q.shape
    assert sq == 1
    _, kh, page_size, _ = pool_k.shape
    dv = pool_v.shape[-1]
    g = h // kh
    n_pages = page_table.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(dk)
    qp = q_positions.astype(jnp.int32)
    qh = q[:, 0].reshape(b, kh, g, dk).astype(jnp.float32)
    table = page_table.astype(jnp.int32)
    if bounded:
        n_live = jnp.clip((jnp.max(qp) + page_size) // page_size, 0, n_pages)
    else:
        n_live = jnp.asarray(n_pages, jnp.int32)

    def body(i, carry):
        m_run, l_run, acc = carry
        pages = jax.lax.dynamic_slice_in_dim(table, i, 1, axis=1)[:, 0]
        kc = pool_k[jnp.maximum(pages, 0)]     # (B, K, page_size, Dk)
        vc = pool_v[jnp.maximum(pages, 0)]
        kp = i * page_size + jnp.arange(page_size, dtype=jnp.int32)[None, :]
        mask = (pages >= 0)[:, None] & (kp <= qp[:, None])
        if window is not None:
            mask &= kp > qp[:, None] - window
        sc = jnp.einsum("bkgd,bkcd->bkgc", qh, kc.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * scale
        sc = jnp.where(mask[:, None, None, :], sc, NEG_INF)
        m_new = jnp.maximum(m_run, sc.max(axis=-1))
        # mask p explicitly: in an all-invalid block m_new stays NEG_INF and
        # exp(NEG_INF - NEG_INF) = 1 would attend uniformly to garbage
        p = jnp.where(mask[:, None, None, :],
                      jnp.exp(sc - m_new[..., None]), 0.0)
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + p.sum(axis=-1)
        pv = jnp.einsum("bkgc,bkcd->bkgd", p, vc.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        return m_new, l_new, acc * corr[..., None] + pv

    m0 = jnp.full((b, kh, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kh, g), jnp.float32)
    a0 = jnp.zeros((b, kh, g, dv), jnp.float32)
    _, l_f, acc = jax.lax.fori_loop(0, n_live, body, (m0, l0, a0))
    out = acc / jnp.maximum(l_f[..., None], 1e-37)
    return out.reshape(b, 1, h, dv).astype(q.dtype)


def decode_attention_paged(q, pool_k, pool_v, q_positions, page_table, *,
                           window=None, interpret=False):
    """Backend dispatch for the paged cache path, by the same rule as
    :func:`decode_attention`."""
    if on_tpu() or interpret:
        return flash_decode_paged(q, pool_k, pool_v, q_positions, page_table,
                                  window=window, interpret=not on_tpu())
    return flash_decode_paged_xla(q, pool_k, pool_v, q_positions, page_table,
                                  window=window)
