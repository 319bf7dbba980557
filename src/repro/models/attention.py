"""Attention: GQA with chunked online-softmax (the XLA-native flash analogue).

One code path serves training, prefill, and decode:

* KV is processed in chunks with running (max, sum, acc) statistics, so the
  live logits footprint is ``O(S_q × kv_chunk)`` instead of ``O(S_q × S_k)``
  — this is what keeps the HLO-bytes roofline term honest on 32k prefills.
* ``q_offset`` may be per-batch (continuous batching / decode).
* ``window`` enables sliding-window (local) attention. For training/prefill
  the *banded* fast path slices only the KV band each q-chunk needs, so FLOPs
  are ``O(S·(window+chunk))`` rather than ``O(S²)``. For decode, local layers
  use a **ring-buffer cache** of size ``window`` (a 500k-token context costs
  O(window) HBM on 5/6 of Gemma-3 layers and *all* RecurrentGemma layers).
* bidirectional (encoder) attention is ``causal=False, window=None``.

A Pallas TPU kernel (``repro.kernels.flash_attention``) implements the same
contract for the perf-critical path; this module is its reference and the
dry-run lowering target.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .params import ParamSpec
from .layers import apply_rope, rmsnorm, rmsnorm_spec

NEG_INF = -1e30


def attention_spec(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    spec = {
        "wq": ParamSpec((d, cfg.n_heads, cfg.head_dim),
                        ("embed", "heads", "head_dim"), init="lecun"),
        "wk": ParamSpec((d, cfg.n_kv_heads, cfg.head_dim),
                        ("embed", "kv_heads", "head_dim"), init="lecun"),
        "wv": ParamSpec((d, cfg.n_kv_heads, cfg.head_dim),
                        ("embed", "kv_heads", "head_dim"), init="lecun"),
        "wo": ParamSpec((cfg.n_heads, cfg.head_dim, d),
                        ("heads", "head_dim", "embed"), init="lecun"),
    }
    if cfg.use_qk_norm:
        spec["q_norm"] = {"scale": ParamSpec((cfg.head_dim,), (None,), init="ones")}
        spec["k_norm"] = {"scale": ParamSpec((cfg.head_dim,), (None,), init="ones")}
    return spec


def _expand_positions(q_offset: jax.Array | int, b: int, s: int) -> jax.Array:
    """-> (B, S) absolute positions."""
    base = jnp.arange(s, dtype=jnp.int32)
    if isinstance(q_offset, int):
        return jnp.broadcast_to(base[None, :] + q_offset, (b, s))
    q_offset = jnp.asarray(q_offset, jnp.int32)
    if q_offset.ndim == 0:
        return jnp.broadcast_to(base[None, :] + q_offset, (b, s))
    return q_offset[:, None] + base[None, :]


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      q_offset: jax.Array | int = 0,
                      k_positions: jax.Array | None = None,
                      causal: bool = True,
                      window: int | None = None,
                      kv_chunk: int = 1024,
                      k_valid: jax.Array | None = None,
                      scale: float | None = None,
                      return_stats: bool = False,
                      score_dtype=jnp.float32):
    """q: (B, Sq, H, Dk); k: (B, Sk, K, Dk); v: (B, Sk, K, Dv), H % K == 0.
    Dv may differ from Dk (MLA decodes attention in the compressed latent).

    ``k_positions``: (B, Sk) absolute positions of cache slots (ring caches);
    default is ``arange(Sk)``. ``k_valid``: (B, Sk) filled-slot mask.
    Returns (B, Sq, H, Dv); accumulates in f32.
    """
    b, sq, h, dh = q.shape
    _, sk, kh, _ = k.shape
    dv = v.shape[-1]
    g = h // kh
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    qh = q.reshape(b, sq, kh, g, dh)
    q_pos = _expand_positions(q_offset, b, sq)

    c = min(kv_chunk, sk)
    n_chunks = -(-sk // c)
    pad = n_chunks * c - sk
    if k_positions is None:
        k_positions = jnp.broadcast_to(
            jnp.arange(sk, dtype=jnp.int32)[None, :], (b, sk))
    if k_valid is None:
        k_valid = jnp.ones((b, sk), bool)
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, ((0, 0), (0, pad)),
                              constant_values=-1)
        k_valid = jnp.pad(k_valid, ((0, 0), (0, pad)))

    # banded fast path: training/prefill sliding-window attention touches only
    # the KV band [q_chunk_start - window, q_chunk_end).
    if (window is not None and causal and sq > 1 and sk == sq and sk > c
            and pad == 0 and dv == dh):
        return _banded_local_attention(qh, k, v, q_pos, window=window,
                                       chunk=c, scale=scale, sq=sq)

    # IMPORTANT: chunks are sliced inside the scan body (dynamic_slice on the
    # loop-invariant operand) rather than pre-stacked as scan xs — stacking
    # would materialize a transposed copy of the entire K/V (for decode, of
    # the entire cache: +2× cache HBM, caught by the dry-run memory analysis).
    def body(carry, i):
        m_run, l_run, acc = carry
        kc = jax.lax.dynamic_slice_in_dim(k, i * c, c, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, i * c, c, axis=1)
        kpos_c = jax.lax.dynamic_slice_in_dim(k_positions, i * c, c, axis=1)
        kval_c = jax.lax.dynamic_slice_in_dim(k_valid, i * c, c, axis=1)
        sdt = jnp.dtype(score_dtype)
        s = jnp.einsum("bqkgd,bckd->bqkgc", qh, kc,
                       preferred_element_type=sdt) * jnp.asarray(scale, sdt)
        qp = q_pos[:, :, None]           # (B, Sq, 1)
        kp = kpos_c[:, None, :]          # (B, 1, C)
        mask = kval_c[:, None, :] & (kp >= 0)
        if causal:
            mask &= kp <= qp
        if window is not None:
            mask &= kp > qp - window
        neg = NEG_INF if sdt == jnp.float32 else -6e4  # bf16-representable
        s = jnp.where(mask[:, :, None, None, :], s, jnp.asarray(neg, sdt))
        m_new = jnp.maximum(m_run, s.max(axis=-1).astype(jnp.float32))
        # probabilities stay in score_dtype (bf16 halves the two dominant
        # S×chunk buffers); running stats stay f32.
        p = jnp.exp(s - m_new[..., None].astype(sdt))
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + p.sum(axis=-1, dtype=jnp.float32)
        pv = jnp.einsum("bqkgc,bckd->bqkgd", p.astype(vc.dtype), vc,
                        preferred_element_type=jnp.float32)
        acc = acc * corr[..., None] + pv
        return (m_new, l_new, acc), None

    # tie the initial carries to the inputs so they inherit the inputs'
    # varying-axes type under shard_map (flash-decode island); constant-folds
    # to plain zeros outside shard_map.
    tie = (q.reshape(-1)[0] * 0 + k.reshape(-1)[0] * 0).astype(jnp.float32)
    m0 = jnp.full((b, sq, kh, g), NEG_INF, jnp.float32) + tie
    l0 = jnp.zeros((b, sq, kh, g), jnp.float32) + tie
    a0 = jnp.zeros((b, sq, kh, g, dv), jnp.float32) + tie
    (m_f, l_f, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                      jnp.arange(n_chunks, dtype=jnp.int32))
    out = acc / jnp.maximum(l_f[..., None], 1e-37)
    out = out.reshape(b, sq, h, dv).astype(q.dtype)
    if return_stats:
        # (B, Sq, H) running max / normalizer — lets callers merge partial
        # attention across sequence shards (flash-decode island).
        return out, m_f.reshape(b, sq, h), l_f.reshape(b, sq, h)
    return out


def _banded_local_attention(qh: jax.Array, k: jax.Array, v: jax.Array,
                            q_pos: jax.Array, *, window: int, chunk: int,
                            scale: float, sq: int) -> jax.Array:
    """Sliding-window attention computing only the needed KV band per q-chunk.
    qh: (B, Sq, K, G, Dh), Sq divisible by ``chunk``."""
    b, _, kh, g, dh = qh.shape
    c = chunk
    n_q = sq // c
    band = -(-window // c) * c + c  # kv band length per q chunk (>= window+c)
    # left-pad k/v so the band slice is always in range
    kp = jnp.pad(k, ((0, 0), (band - c, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (band - c, 0), (0, 0), (0, 0)))

    def per_q_chunk(i):
        qc = jax.lax.dynamic_slice_in_dim(qh, i * c, c, axis=1)
        pos_c = jax.lax.dynamic_slice_in_dim(q_pos, i * c, c, axis=1)
        kc = jax.lax.dynamic_slice_in_dim(kp, i * c, band, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(vp, i * c, band, axis=1)
        k_pos = i * c - (band - c) + jnp.arange(band, dtype=jnp.int32)
        s = jnp.einsum("bqkgd,bckd->bqkgc", qc, kc,
                       preferred_element_type=jnp.float32) * scale
        mask = (k_pos[None, None, :] <= pos_c[:, :, None]) & \
               (k_pos[None, None, :] > pos_c[:, :, None] - window) & \
               (k_pos[None, None, :] >= 0)
        s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
        m = s.max(axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        o = jnp.einsum("bqkgc,bckd->bqkgd", p.astype(vc.dtype), vc,
                       preferred_element_type=jnp.float32)
        return (o / jnp.maximum(p.sum(-1)[..., None], 1e-37)).astype(k.dtype)

    outs = jax.lax.map(per_q_chunk, jnp.arange(n_q, dtype=jnp.int32))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, n_q * c, kh, g, dh)
    return out[:, :sq].reshape(b, sq, kh * g, dh)


# ---------------------------------------------------------------------------
# Attention block: projections + RoPE + cache management
# ---------------------------------------------------------------------------


def attention_block(params: dict, cfg: ModelConfig, x: jax.Array, *,
                    kind: str,
                    positions: jax.Array | int = 0,
                    cache: dict | None = None,
                    cache_index: jax.Array | None = None,
                    dist=None,
                    pages: jax.Array | None = None) -> tuple[jax.Array, dict | None]:
    """Projections + RoPE + attention (+ KV-cache update for decode).

    ``cache``: {"k": (B, S_cache, K, Dh), "v": ...}. If ``S_cache == window``
    for a local layer, the cache is treated as a **ring buffer**. A paged
    cache instead holds {"pool_k": (P, K, page_size, Dh), "pool_v": ...}
    and requires ``pages``: the (B, pages_per_slot) int32 page table
    (-1 = unbound; page 0 is the allocator's trash page).
    ``cache_index``: scalar int32 — count of tokens already cached.
    """
    b, s, d = x.shape
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
    if cfg.use_qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.rms_eps)
        k = rmsnorm(params["k_norm"], k, cfg.rms_eps)
    theta = cfg.rope_theta
    if kind == "attn" and cfg.rope_theta_global is not None:
        theta = cfg.rope_theta_global
    window = cfg.window_size if kind == "local" else None
    if not cfg.encoder_only:
        pos = _expand_positions(positions, b, s)
        q = apply_rope(q, pos, theta)
        k = apply_rope(k, pos, theta)

    if cache is None:
        out = chunked_attention(q, k, v, q_offset=0,
                                causal=not cfg.encoder_only,
                                window=window, kv_chunk=cfg.kv_chunk,
                                score_dtype=jnp.dtype(cfg.score_dtype))
        y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
        return y, None

    assert cache_index is not None
    cache_index = jnp.asarray(cache_index, jnp.int32)
    per_slot = cache_index.ndim == 1  # continuous batching: (B,) positions

    if "pool_k" in cache:  # paged KV cache (serving tier)
        assert per_slot and s == 1 and pages is not None
        new_cache, out = _paged_decode(cfg, q, k, v, cache, cache_index,
                                       pages, window)
        y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
        return y, new_cache

    s_cache = cache["k"].shape[1]
    is_ring = window is not None and s_cache == window
    cdt = cache["k"].dtype
    if is_ring:
        # ring write: token at absolute position p lands in slot p % window.
        take = min(s, window)
        if per_slot:
            rows = jnp.arange(b, dtype=jnp.int32)[:, None]
            slots = (cache_index[:, None] +
                     jnp.arange(s - take, s, dtype=jnp.int32)[None, :]) % window
            ck = cache["k"].at[rows, slots].set(k[:, s - take:].astype(cdt))
            cv = cache["v"].at[rows, slots].set(v[:, s - take:].astype(cdt))
            t_new = (cache_index + s)[:, None]                  # (B, 1)
        else:
            slots = (cache_index +
                     jnp.arange(s - take, s, dtype=jnp.int32)) % window
            ck = cache["k"].at[:, slots].set(k[:, s - take:].astype(cdt))
            cv = cache["v"].at[:, slots].set(v[:, s - take:].astype(cdt))
            t_new = jnp.full((b, 1), cache_index + s, jnp.int32)
        # slot j holds position t_new - 1 - ((t_new - 1 - j) mod window).
        j = jnp.arange(window, dtype=jnp.int32)[None, :]
        k_positions = t_new - 1 - jnp.mod(t_new - 1 - j, window)
        k_valid = k_positions >= 0
    else:
        if per_slot:
            rows = jnp.arange(b, dtype=jnp.int32)[:, None]
            slots = cache_index[:, None] + jnp.arange(s, dtype=jnp.int32)
            ck = cache["k"].at[rows, slots].set(k.astype(cdt))
            cv = cache["v"].at[rows, slots].set(v.astype(cdt))
            end = (cache_index + s)[:, None]
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cdt), cache_index, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cdt), cache_index, axis=1)
            end = jnp.full((b, 1), cache_index + s, jnp.int32)
        k_positions = jnp.broadcast_to(
            jnp.arange(s_cache, dtype=jnp.int32)[None, :], (b, s_cache))
        k_valid = k_positions < end
    new_cache = {"k": ck, "v": cv}
    if (dist is not None and dist.has("flash_decode") and s == 1
            and not is_ring):
        # sequence-parallel decode: cache stays seq-sharded on `model`;
        # partial softmax stats merge with one small psum per layer.
        out = dist.decode_attention(q, ck.astype(dt), cv.astype(dt),
                                    k_positions, k_valid, window=window,
                                    kv_chunk=cfg.kv_chunk,
                                    q_offset=positions)
    elif (cfg.decode_kernel == "flash" and s == 1 and per_slot
          and dist is None):
        # serving hot path: fused split-KV flash-decode. The -1-invalid
        # position encoding folds k_valid into k_positions; ring caches
        # (slot != position) disable the occupancy-bounded trip count.
        from repro.kernels.flash_decode import decode_attention
        out = decode_attention(
            q, ck.astype(dt), cv.astype(dt), cache_index,
            jnp.where(k_valid, k_positions, -1), window=window,
            interpret=cfg.kernel_interpret, bounded=not is_ring)
    else:
        out = chunked_attention(q, ck.astype(dt), cv.astype(dt),
                                q_offset=positions, k_positions=k_positions,
                                causal=True, window=window,
                                kv_chunk=cfg.kv_chunk, k_valid=k_valid)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    return y, new_cache


def _paged_decode(cfg: ModelConfig, q: jax.Array, k: jax.Array,
                  v: jax.Array, cache: dict, positions: jax.Array,
                  pages: jax.Array, window: int | None
                  ) -> tuple[dict, jax.Array]:
    """One decode step against a paged KV cache.

    The new token is scattered into its slot's current page (slots whose
    table row is unbound clamp to the reserved trash page 0), then attention
    reads through the page table. ``decode_kernel="flash"`` uses the fused
    paged kernel; "chunked" gathers the logical view and runs the reference
    — pages are bound in logical order, so offsets past a slot's position
    hold garbage but are causally masked (``k_pos > q_pos``).
    """
    b = q.shape[0]
    dt = q.dtype
    cdt = cache["pool_k"].dtype
    page_size = cache["pool_k"].shape[2]
    rows = jnp.arange(b, dtype=jnp.int32)
    page = pages[rows, positions // page_size]
    page = jnp.maximum(page, 0)
    off = positions % page_size
    ck = cache["pool_k"].at[page, :, off].set(k[:, 0].astype(cdt))
    cv = cache["pool_v"].at[page, :, off].set(v[:, 0].astype(cdt))
    new_cache = {"pool_k": ck, "pool_v": cv}
    if cfg.decode_kernel == "flash":
        from repro.kernels.flash_decode import decode_attention_paged
        out = decode_attention_paged(q, ck.astype(dt), cv.astype(dt),
                                     positions, pages, window=window,
                                     interpret=cfg.kernel_interpret)
        return new_cache, out
    n_pages = pages.shape[1]
    tbl = jnp.maximum(pages, 0)
    kh, dk = ck.shape[1], ck.shape[3]
    dv = cv.shape[3]
    # (B, n_pages, K, page_size, D) -> logical (B, n_pages*page_size, K, D)
    k_lin = ck[tbl].transpose(0, 1, 3, 2, 4).reshape(
        b, n_pages * page_size, kh, dk)
    v_lin = cv[tbl].transpose(0, 1, 3, 2, 4).reshape(
        b, n_pages * page_size, kh, dv)
    kp = (jnp.arange(n_pages, dtype=jnp.int32)[:, None] * page_size +
          jnp.arange(page_size, dtype=jnp.int32)[None, :])
    kp = jnp.where(pages[:, :, None] >= 0, kp[None], -1)
    kp = kp.reshape(b, n_pages * page_size)
    out = chunked_attention(q, k_lin.astype(dt), v_lin.astype(dt),
                            q_offset=positions, k_positions=kp,
                            causal=True, window=window,
                            kv_chunk=cfg.kv_chunk, k_valid=kp >= 0,
                            score_dtype=jnp.dtype(cfg.score_dtype))
    return new_cache, out


def init_kv_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                  dtype: Any) -> dict:
    """Per-layer KV cache prototype. Local layers get a ring buffer of size
    ``window`` (when max_len exceeds it)."""
    length = max_len
    if kind == "local":
        length = min(max_len, cfg.window_size)
    return {
        "k": jnp.zeros((batch, length, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, length, cfg.n_kv_heads, cfg.head_dim), dtype),
    }
