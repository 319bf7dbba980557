"""Multi-head Latent Attention (DeepSeek-V3, arXiv:2412.19437).

Queries go through a LoRA bottleneck (``q_lora_rank``), or are projected
directly when the config has none (Moonlight); keys/values are compressed
into a small latent (``kv_lora_rank``) plus one shared RoPE head.
Training/prefill materializes per-head K/V; decode uses the **absorbed**
formulation — attention runs directly in the compressed latent, so the KV
cache is ``kv_lora_rank + rope_head_dim`` floats per token *total* (not per
head), the property that makes 128-head decode at 32k context cheap. The
serving tier keeps that row in a paged latent pool (``pool_latent``), which
every query head reads as its one KV head: K is the whole row, V its first
``kv_lora_rank`` lanes.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .attention import (_expand_positions, _pad_last, chunked_attention,
                        paged_row, paged_view, write_paged_rows)
from .config import ModelConfig
from .layers import apply_rope, rmsnorm
from .params import ParamSpec


def mla_spec(cfg: ModelConfig) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk = m.nope_head_dim + m.rope_head_dim
    if m.q_lora_rank is None:
        q = {"w_q": ParamSpec((d, h, qk), ("embed", "heads", "head_dim"),
                              init="lecun")}
    else:
        q = {
            "w_dq": ParamSpec((d, m.q_lora_rank), ("embed", "lora"),
                              init="lecun"),
            "q_norm": {"scale": ParamSpec((m.q_lora_rank,), (None,),
                                          init="ones")},
            "w_uq": ParamSpec((m.q_lora_rank, h, qk),
                              ("lora", "heads", "head_dim"), init="lecun"),
        }
    return {
        **q,
        "w_dkv": ParamSpec((d, m.kv_lora_rank + m.rope_head_dim),
                           ("embed", "lora"), init="lecun"),
        "kv_norm": {"scale": ParamSpec((m.kv_lora_rank,), (None,), init="ones")},
        "w_uk": ParamSpec((m.kv_lora_rank, h, m.nope_head_dim),
                          ("lora", "heads", "head_dim"), init="lecun"),
        "w_uv": ParamSpec((m.kv_lora_rank, h, m.v_head_dim),
                          ("lora", "heads", "head_dim"), init="lecun"),
        "w_o": ParamSpec((h, m.v_head_dim, d), ("heads", "head_dim", "embed"),
                         init="lecun"),
    }


def _project_q(params: dict, cfg: ModelConfig, x: jax.Array,
               positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    """-> (q_nope (B,S,H,nope), q_rope (B,S,H,rope))."""
    m = cfg.mla
    if "w_q" in params:
        q = jnp.einsum("bsd,dhk->bshk", x, params["w_q"])
    else:
        cq = rmsnorm(params["q_norm"], x @ params["w_dq"], cfg.rms_eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, params["w_uq"])
    q_nope, q_rope = jnp.split(q, [m.nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _compress_kv(params: dict, cfg: ModelConfig, x: jax.Array,
                 positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    """-> (c_kv (B,S,R), k_rope (B,S,1,rope)) — exactly what the cache holds."""
    m = cfg.mla
    dkv = x @ params["w_dkv"]
    c_kv, k_rope = jnp.split(dkv, [m.kv_lora_rank], axis=-1)
    c_kv = rmsnorm(params["kv_norm"], c_kv, cfg.rms_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)
    return c_kv, k_rope


def mla_block(params: dict, cfg: ModelConfig, x: jax.Array, *,
              positions: jax.Array | int = 0,
              cache: dict | None = None,
              cache_index: jax.Array | None = None,
              dist=None,
              pages: jax.Array | None = None) -> tuple[jax.Array, dict | None]:
    """MLA attention block. ``cache``: {"c_kv": (B, S, R), "k_rope": (B, S,
    rope)}, or a paged {"pool_latent": ...} with the page table ``pages``
    (see :func:`_paged_latent_decode`)."""
    m = cfg.mla
    b, s, _ = x.shape
    dt = x.dtype
    pos = _expand_positions(positions if cache is not None else 0, b, s)
    q_nope, q_rope = _project_q(params, cfg, x, pos)
    c_kv, k_rope = _compress_kv(params, cfg, x, pos)
    scale = 1.0 / math.sqrt(m.nope_head_dim + m.rope_head_dim)

    if cache is None:
        # materialized path (training / full prefill)
        k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, params["w_uk"])
        v = jnp.einsum("bsr,rhk->bshk", c_kv, params["w_uv"])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, s, cfg.n_heads, m.rope_head_dim))],
            axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = chunked_attention(q, k, v, causal=True, kv_chunk=cfg.kv_chunk,
                                scale=scale,
                                score_dtype=jnp.dtype(cfg.score_dtype))
        y = jnp.einsum("bshk,hkd->bsd", out, params["w_o"].astype(dt))
        return y, None

    # absorbed decode: attention in the compressed latent.
    assert cache_index is not None
    cache_index = jnp.asarray(cache_index, jnp.int32)
    if "pool_latent" in cache:  # paged latent cache (serving tier)
        assert cache_index.ndim == 1 and s == 1 and pages is not None
        new_cache, ctx = _paged_latent_decode(params, cfg, q_nope, q_rope,
                                              c_kv, k_rope, cache,
                                              cache_index, pages, scale)
        y = jnp.einsum("bshr,rhk,hkd->bsd", ctx, params["w_uv"].astype(dt),
                       params["w_o"].astype(dt))
        return y, new_cache
    cdt = cache["c_kv"].dtype
    if cache_index.ndim == 1:  # continuous batching: per-slot positions
        rows = jnp.arange(b, dtype=jnp.int32)[:, None]
        slots = cache_index[:, None] + jnp.arange(s, dtype=jnp.int32)
        ck = cache["c_kv"].at[rows, slots].set(c_kv.astype(cdt))
        cr = cache["k_rope"].at[rows, slots].set(
            k_rope[:, :, 0, :].astype(cdt))
        end = (cache_index + s)[:, None]
    else:
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_kv.astype(cdt), cache_index, axis=1)
        cr = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], k_rope[:, :, 0, :].astype(cdt), cache_index,
            axis=1)
        end = None
    new_cache = {"c_kv": ck, "k_rope": cr}
    s_cache = ck.shape[1]
    # q_eff[h] = q_nope[h] @ w_uk[h]^T  -> query against c_kv directly
    q_eff = jnp.einsum("bshk,rhk->bshr", q_nope, params["w_uk"].astype(dt))
    q_cat = jnp.concatenate([q_eff, q_rope], axis=-1)          # (B,S,H,R+rope)
    k_cat = jnp.concatenate([ck, cr], axis=-1)[:, :, None, :]  # (B,Sc,1,R+rope)
    v_lat = ck[:, :, None, :]                                  # (B,Sc,1,R)
    if end is None:
        end = jnp.full((b, 1), cache_index + s, jnp.int32)
    k_valid = jnp.arange(s_cache, dtype=jnp.int32)[None, :] < end
    k_valid = jnp.broadcast_to(k_valid, (b, s_cache))
    if dist is not None and dist.has("flash_decode") and s == 1:
        k_positions = jnp.broadcast_to(
            jnp.arange(s_cache, dtype=jnp.int32)[None, :], (b, s_cache))
        ctx = dist.decode_attention(q_cat.astype(dt), k_cat.astype(dt),
                                    v_lat.astype(dt), k_positions, k_valid,
                                    kv_chunk=cfg.kv_chunk,
                                    q_offset=positions, scale=scale)
    else:
        ctx = chunked_attention(q_cat.astype(dt), k_cat.astype(dt),
                                v_lat.astype(dt), q_offset=positions,
                                causal=True, kv_chunk=cfg.kv_chunk,
                                k_valid=k_valid, scale=scale)   # (B,S,H,R)
    # absorb the value up-projection, then the output projection
    y = jnp.einsum("bshr,rhk,hkd->bsd", ctx, params["w_uv"].astype(dt),
                   params["w_o"].astype(dt))
    return y, new_cache


def _paged_latent_decode(params: dict, cfg: ModelConfig, q_nope, q_rope,
                         c_kv, k_rope, cache: dict, positions: jax.Array,
                         pages: jax.Array, scale: float
                         ) -> tuple[dict, jax.Array]:
    """One absorbed decode step against a paged latent pool: one layer's
    ``(P, 1, page_size, D)`` or the stack ``(L, P, 1, page_size, D)`` at
    ``cache["layer"]``, D the row ``[c_kv | k_rope]`` rounded up to whole
    lane tiles (``paged_row``). Each slot's row is written in place into
    its page (:func:`write_paged_rows`); the absorbed query
    ``[q_nope @ w_uk | q_rope]`` of every head attends over the rows, the
    values being their first ``kv_lora_rank`` lanes. -> (new cache, context
    (B, 1, H, kv_lora_rank))."""
    m = cfg.mla
    dt = q_nope.dtype
    pool = cache["pool_latent"]
    layer = cache.get("layer")
    row = jnp.concatenate([c_kv[:, 0], k_rope[:, 0, 0]], axis=-1)
    row = _pad_last(row, pool.shape[-1])[:, None]          # (B, 1, D)
    (pool,) = write_paged_rows((pool,), (row,), positions, pages, layer)
    new_cache = {"pool_latent": pool}
    q_eff = jnp.einsum("bshk,rhk->bshr", q_nope, params["w_uk"].astype(dt))
    q_cat = jnp.concatenate([q_eff, q_rope], axis=-1)     # (B, 1, H, R+rope)
    r = m.kv_lora_rank
    if cfg.decode_kernel == "flash":
        from repro.kernels.flash_decode import decode_attention_latent
        ctx = decode_attention_latent(q_cat.astype(dt), pool.astype(dt),
                                      positions, pages, layer, dv=r,
                                      scale=scale,
                                      interpret=cfg.kernel_interpret)
        return new_cache, ctx
    lin, kp = paged_view(pool, pages, layer)               # (B, S, 1, D)
    ctx = chunked_attention(q_cat.astype(dt),
                            lin[..., :r + m.rope_head_dim].astype(dt),
                            lin[..., :r].astype(dt), q_offset=positions,
                            k_positions=kp, causal=True,
                            kv_chunk=cfg.kv_chunk, k_valid=kp >= 0,
                            scale=scale,
                            score_dtype=jnp.dtype(cfg.score_dtype))
    return new_cache, ctx


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, m.rope_head_dim), dtype),
    }


def init_paged_mla_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                         dtype) -> dict:
    """One layer's paged latent pool, ``(n_pages, 1, page_size, D)`` with
    D the latent row ``kv_lora_rank + rope_head_dim`` rounded up to whole
    128-lane tiles, so that the TPU's default layout is row-major."""
    m = cfg.mla
    row = paged_row(m.kv_lora_rank + m.rope_head_dim)
    return {"pool_latent": jnp.zeros((n_pages, 1, page_size, row), dtype)}
