"""The composable model stack.

Layers are generated from ``cfg.layer_pattern`` cycled over ``n_layers``. The
repeating *period* (e.g. Gemma-3's ``(local×5, global)``; RecurrentGemma's
``(rglru, rglru, local)``) is the ``lax.scan`` unit: parameters (and caches)
for the full periods are stacked on a leading ``layers`` axis so the lowered
HLO contains **one** period body regardless of depth — this is what keeps
dry-run compiles of 61-layer models tractable and the compiled program small.
Remainder layers (``n_layers % period``) are applied unrolled after the scan.

``dist`` (a ``repro.sharding.DistContext`` or None) switches the MoE between
the single-device capacity path and the expert-parallel ``shard_map`` island.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from .attention import attention_block, attention_spec, init_kv_cache
from .config import ModelConfig
from .layers import embed, embedding_spec, mlp, mlp_spec, rmsnorm, rmsnorm_spec, unembed
from .mla import init_mla_cache, mla_block, mla_spec
from .moe import moe_block, moe_spec
from .params import ParamSpec, stack_specs
from .rglru import init_rglru_cache, rglru_block, rglru_spec
from .ssd import init_ssd_cache, ssd_block, ssd_spec


def _has_mlp(cfg: ModelConfig, kind: str) -> bool:
    if kind in ("attn", "local"):
        return True
    return cfg.d_ff > 0


def block_spec(cfg: ModelConfig, kind: str) -> dict:
    d = cfg.d_model
    spec: dict = {"norm1": rmsnorm_spec(d)}
    if kind in ("attn", "local"):
        spec["mix"] = mla_spec(cfg) if cfg.mla is not None else attention_spec(cfg)
    elif kind == "ssd":
        spec["mix"] = ssd_spec(cfg)
    elif kind == "rglru":
        spec["mix"] = rglru_spec(cfg)
    else:
        raise ValueError(f"unknown layer kind {kind}")
    if _has_mlp(cfg, kind):
        spec["norm2"] = rmsnorm_spec(d)
        spec["ffn"] = moe_spec(cfg) if cfg.moe is not None else mlp_spec(cfg)
    return spec


def block_apply(params: dict, cfg: ModelConfig, kind: str, x: jax.Array, *,
                positions: jax.Array | int = 0,
                cache: dict | None = None,
                cache_index: jax.Array | None = None,
                dist: Any = None,
                decode: bool = False,
                pages: jax.Array | None = None) -> tuple[jax.Array, dict | None, jax.Array]:
    """One residual block. Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(params["norm1"], x, cfg.rms_eps)
    if kind in ("attn", "local"):
        if cfg.mla is not None:
            y, new_cache = mla_block(params["mix"], cfg, h,
                                     positions=positions, cache=cache,
                                     cache_index=cache_index, dist=dist)
        else:
            y, new_cache = attention_block(params["mix"], cfg, h, kind=kind,
                                           positions=positions, cache=cache,
                                           cache_index=cache_index,
                                           dist=dist, pages=pages)
    elif kind == "ssd":
        y, new_cache = ssd_block(params["mix"], cfg, h, cache=cache)
    else:  # rglru
        y, new_cache = rglru_block(params["mix"], cfg, h, cache=cache)
    x = x + y
    if _has_mlp(cfg, kind):
        h = rmsnorm(params["norm2"], x, cfg.rms_eps)
        if cfg.moe is not None:
            if dist is not None:
                f, aux = dist.moe_island(params["ffn"], cfg, h, decode=decode)
            else:
                f, aux = moe_block(params["ffn"], cfg, h, impl="capacity",
                                   dropless=decode)
        else:
            f = mlp(params["ffn"], cfg, h)
        x = x + f
    if dist is not None:
        x = dist.constrain_activation(x)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Full-model spec
# ---------------------------------------------------------------------------


def model_spec(cfg: ModelConfig) -> dict:
    period_spec = {str(i): block_spec(cfg, k)
                   for i, k in enumerate(cfg.layer_pattern)}
    spec: dict = {
        "embed": embedding_spec(cfg),
        "final_norm": rmsnorm_spec(cfg.d_model),
    }
    if cfg.n_periods > 0:
        spec["periods"] = stack_specs(period_spec, cfg.n_periods)
    if cfg.n_remainder:
        spec["tail"] = {str(i): block_spec(cfg, cfg.layer_pattern[i])
                        for i in range(cfg.n_remainder)}
    if cfg.frontend is not None:
        spec["frontend"] = {
            "w": ParamSpec((cfg.frontend.input_dim, cfg.d_model),
                           ("ff", "embed"), init="lecun"),
            "b": ParamSpec((cfg.d_model,), ("embed",), init="zeros"),
        }
    return spec


# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------


def _apply_period(params_p: dict, cfg: ModelConfig, x: jax.Array, *,
                  positions, caches_p, cache_index, dist, decode=False,
                  pages=None):
    """Apply one period (len(layer_pattern) blocks). caches_p: dict per slot."""
    new_caches = {}
    aux = jnp.zeros((), jnp.float32)
    for i, kind in enumerate(cfg.layer_pattern):
        c = caches_p.get(str(i)) if caches_p is not None else None
        x, nc, a = block_apply(params_p[str(i)], cfg, kind, x,
                               positions=positions, cache=c,
                               cache_index=cache_index, dist=dist,
                               decode=decode, pages=pages)
        aux = aux + a
        if nc is not None:
            new_caches[str(i)] = nc
    return x, new_caches, aux


def forward(params: dict, cfg: ModelConfig, batch: dict, *,
            caches: dict | None = None,
            cache_index: jax.Array | None = None,
            dist: Any = None,
            remat: str = "none",
            unroll: int | bool = 1,
            return_hidden: bool = False,
            pages: jax.Array | None = None
            ) -> tuple[jax.Array, dict | None, jax.Array]:
    """Run the stack.

    ``batch``: {"tokens": (B, S) int32} and/or {"embeds": (B, S, input_dim)}
    for stub frontends; VLM concatenates projected patch embeds before text.
    ``caches``: {"periods": stacked-cache pytree, "tail": {...}} or None.
    ``pages``: (B, pages_per_slot) int32 page table when ``caches`` came
    from :func:`init_paged_caches` (shared by every paged layer — slot
    positions advance uniformly across the stack).
    Returns (logits (B, S, vocab) [text positions only for VLM], new_caches,
    aux_loss).
    """
    decode = caches is not None
    if cfg.frontend is not None and cfg.frontend.kind == "audio_frames":
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype)) @ params["frontend"]["w"] \
            + params["frontend"]["b"]
        n_prefix = 0
    elif cfg.frontend is not None and cfg.frontend.kind == "vit_patches":
        x_txt = embed(params["embed"], cfg, batch["tokens"])
        if "embeds" in batch and batch["embeds"] is not None:
            x_img = batch["embeds"].astype(jnp.dtype(cfg.dtype)) @ \
                params["frontend"]["w"] + params["frontend"]["b"]
            x = jnp.concatenate([x_img, x_txt], axis=1)
            n_prefix = x_img.shape[1]
        else:  # decode steps carry no image
            x = x_txt
            n_prefix = 0
    else:
        x = embed(params["embed"], cfg, batch["tokens"])
        n_prefix = 0
    if dist is not None:
        x = dist.constrain_activation(x)

    positions: jax.Array | int = cache_index if decode else 0
    aux_total = jnp.zeros((), jnp.float32)
    new_caches: dict = {}

    if cfg.n_periods > 0:
        params_p = params["periods"]
        caches_p = caches.get("periods") if decode else None

        def body(carry, xs):
            h, auxc = carry
            p_i, c_i = xs
            h, nc, a = _apply_period(p_i, cfg, h, positions=positions,
                                     caches_p=c_i, cache_index=cache_index,
                                     dist=dist, decode=decode, pages=pages)
            return (h, auxc + a), nc

        if remat != "none":
            policy = {
                "full": None,
                "dots": jax.checkpoint_policies.checkpoint_dots,
                "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
            }[remat]
            body = jax.checkpoint(body, policy=policy,
                                  prevent_cse=False)
        (x, aux_total), nc_stack = jax.lax.scan(body, (x, aux_total),
                                                (params_p, caches_p),
                                                unroll=unroll)
        if decode:
            new_caches["periods"] = nc_stack

    if cfg.n_remainder:
        caches_t = caches.get("tail") if decode else None
        new_tail = {}
        for i in range(cfg.n_remainder):
            kind = cfg.layer_pattern[i]
            c = caches_t.get(str(i)) if caches_t is not None else None
            x, nc, a = block_apply(params["tail"][str(i)], cfg, kind, x,
                                   positions=positions, cache=c,
                                   cache_index=cache_index, dist=dist,
                                   decode=decode, pages=pages)
            aux_total = aux_total + a
            if nc is not None:
                new_tail[str(i)] = nc
        if decode:
            new_caches["tail"] = new_tail

    x = rmsnorm(params["final_norm"], x, cfg.rms_eps)
    if n_prefix:
        x = x[:, n_prefix:]  # loss/logits over text positions only (VLM)
    if return_hidden:  # fused-CE path computes unembed inside its island
        return x, (new_caches if decode else None), aux_total
    logits = unembed(params["embed"], cfg, x)
    return logits, (new_caches if decode else None), aux_total


# ---------------------------------------------------------------------------
# Cache init
# ---------------------------------------------------------------------------


def _cache_for(cfg: ModelConfig, kind: str, batch: int, max_len: int,
               dtype) -> dict | None:
    if kind in ("attn", "local"):
        if cfg.mla is not None:
            return init_mla_cache(cfg, batch, max_len, dtype)
        return init_kv_cache(cfg, kind, batch, max_len, dtype)
    if kind == "ssd":
        return init_ssd_cache(cfg, batch, dtype)
    if kind == "rglru":
        return init_rglru_cache(cfg, batch, dtype)
    return None


def init_caches(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    """Decode cache pytree matching the scan layout of :func:`forward`."""
    out: dict = {}
    if cfg.n_periods > 0:
        per = {}
        for i, kind in enumerate(cfg.layer_pattern):
            c = _cache_for(cfg, kind, batch, max_len, dtype)
            if c is not None:
                per[str(i)] = jax.tree.map(
                    lambda a: jnp.broadcast_to(
                        a[None], (cfg.n_periods,) + a.shape).copy(), c)
        out["periods"] = per
    if cfg.n_remainder:
        tail = {}
        for i in range(cfg.n_remainder):
            kind = cfg.layer_pattern[i]
            c = _cache_for(cfg, kind, batch, max_len, dtype)
            if c is not None:
                tail[str(i)] = c
        out["tail"] = tail
    return out


def paged_layout(max_len: int, page_size: int, batch: int,
                 n_pages: int | None = None) -> tuple[int, int]:
    """(pages_per_slot, pool_pages) for a paged cache. The default pool is
    full-reservation-equivalent plus the reserved trash page; serving passes
    a smaller pool to oversubscribe (long-context slots no longer reserve
    ``max_len`` up front)."""
    pages_per_slot = -(-max_len // page_size)
    if n_pages is None:
        n_pages = batch * pages_per_slot + 1
    return pages_per_slot, n_pages


def _paged_cache_for(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     dtype, *, page_size: int, n_pages: int) -> dict | None:
    if kind in ("attn", "local"):
        if kind == "local" and min(max_len, cfg.window_size) < max_len:
            # ring buffers are already O(window); keep them dense.
            return init_kv_cache(cfg, kind, batch, max_len, dtype)
        return {
            "pool_k": jnp.zeros((n_pages, cfg.n_kv_heads, page_size,
                                 cfg.head_dim), dtype),
            "pool_v": jnp.zeros((n_pages, cfg.n_kv_heads, page_size,
                                 cfg.head_dim), dtype),
        }
    return _cache_for(cfg, kind, batch, max_len, dtype)


def init_paged_caches(cfg: ModelConfig, batch: int, max_len: int, dtype, *,
                      page_size: int = 64,
                      n_pages: int | None = None) -> dict:
    """Decode cache pytree with paged KV for the full-context attention
    layers: physical pools ``(n_pages, K, page_size, Dh)`` indexed through
    the page table that :func:`forward` takes as ``pages``. Ring (local)
    and recurrent (ssd/rglru) caches keep their dense layout — they are
    already O(window) / O(1) per slot. Page 0 is reserved as the trash page
    for writes from unbound slots."""
    if cfg.mla is not None:
        raise NotImplementedError("paged KV cache with MLA latent caches")
    _, n_pages = paged_layout(max_len, page_size, batch, n_pages)
    kw = dict(page_size=page_size, n_pages=n_pages)
    out: dict = {}
    if cfg.n_periods > 0:
        per = {}
        for i, kind in enumerate(cfg.layer_pattern):
            c = _paged_cache_for(cfg, kind, batch, max_len, dtype, **kw)
            if c is not None:
                per[str(i)] = jax.tree.map(
                    lambda a: jnp.broadcast_to(
                        a[None], (cfg.n_periods,) + a.shape).copy(), c)
        out["periods"] = per
    if cfg.n_remainder:
        tail = {}
        for i in range(cfg.n_remainder):
            kind = cfg.layer_pattern[i]
            c = _paged_cache_for(cfg, kind, batch, max_len, dtype, **kw)
            if c is not None:
                tail[str(i)] = c
        out["tail"] = tail
    return out


def cache_shapes(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Any:
    """ShapeDtypeStruct tree of the decode cache (dry-run input spec)."""
    return jax.eval_shape(lambda: init_caches(cfg, batch, max_len, dtype))
