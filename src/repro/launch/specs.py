"""input_specs(): ShapeDtypeStruct stand-ins + shardings for every
(architecture × shape) dry-run cell — weak-type-correct, shardable, zero
device allocation.

Each cell resolves to a :class:`CellSpec`: the step callable, its abstract
arguments, in/out shardings, and donation — everything ``dryrun.py`` needs to
``jit(...).lower(...).compile()``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import Shape, get_config
from repro.models.config import ModelConfig
from repro.models.transformer import init_caches
from repro.optim import OptimizerConfig
from repro.sharding import DistContext, state_axes
from repro.train.step import (make_prefill_step, make_serve_step,
                              make_train_step, train_state_shapes)


@dataclass
class CellSpec:
    fn: Callable
    args: tuple
    in_shardings: tuple
    out_shardings: Any
    donate_argnums: tuple[int, ...] = ()
    static_notes: dict = field(default_factory=dict)


def optimizer_analytic_costs(cfg: ModelConfig, ocfg: OptimizerConfig,
                             accum_dtype: str, n_devices: int) -> dict:
    """Per-device FLOPs/bytes of the AdamW apply (pure elementwise over
    sharded state — no collectives). Counted analytically because the
    costing compiles cover only the fwd/bwd microbatch."""
    n = cfg.param_count(active_only=False)
    p_b = jnp.dtype(cfg.dtype).itemsize
    m_b = jnp.dtype(ocfg.moment_dtype).itemsize
    g_b = jnp.dtype(accum_dtype).itemsize
    v_b = 0.01 * m_b if ocfg.factored_v else m_b
    mst_b = (0 if ocfg.master_dtype == "none"
             else jnp.dtype(ocfg.master_dtype).itemsize)
    per_param_bytes = (g_b            # read grads
                       + 2 * p_b      # read + write params
                       + 2 * m_b      # read + write m
                       + 2 * v_b      # read + write v
                       + 2 * mst_b)   # read + write master
    return {
        "flops_per_device": 12.0 * n / n_devices,
        "bytes_per_device": per_param_bytes * n / n_devices,
        "collective_bytes": 0.0,
    }


def optimizer_for(cfg: ModelConfig) -> OptimizerConfig:
    """Memory policy per scale (see DESIGN.md / EXPERIMENTS.md §Dry-run):
    the 671B config uses bf16 moments + factored second moment and no
    separate master copy — plain fp32 Adam does not fit 256×16 GB."""
    if cfg.name.startswith("deepseek"):
        return OptimizerConfig(moment_dtype="bfloat16", factored_v=True,
                               master_dtype="none")
    return OptimizerConfig()


def train_knobs(cfg: ModelConfig) -> dict:
    """remat / microbatch / accum dtype per arch for the train_4k cell.

    µ is sized so the per-microbatch fp32 logits working set (the CE loss
    block, ~15-19 logit-sized buffers live through backward — measured via
    memory_analysis bisection) stays within HBM: large-vocab/small-d archs
    (Gemma-3, InternVL) need µ=16."""
    if cfg.name.startswith("deepseek"):
        return {"remat": "full", "microbatch": 16, "accum_dtype": "bfloat16"}
    if cfg.name.startswith(("moonlight",)):
        return {"remat": "full", "microbatch": 8, "accum_dtype": "float32"}
    if cfg.padded_vocab >= 128_000 and cfg.d_model <= 4096:
        return {"remat": "full", "microbatch": 16, "accum_dtype": "float32"}
    return {"remat": "full", "microbatch": 4, "accum_dtype": "float32"}


def resolve_knobs(cfg: ModelConfig, dist: DistContext, global_batch: int,
                  overrides: dict | None = None) -> dict:
    """Clamp µ so each microbatch still shards over *all* batch axes —
    µ=16 on a 2×16×16 mesh would leave microbatches of 16 shardable over
    the pod axis only (16× per-device activation blowup, caught by the
    multi-pod dry-run)."""
    knobs = dict(train_knobs(cfg), **(overrides or {}))
    from repro.sharding.context import _size
    n_shards = _size(dist.mesh, dist.batch_axes)
    mu_max = max(1, global_batch // n_shards)
    mu = min(int(knobs.get("microbatch") or 1), mu_max)
    while mu > 1 and (global_batch // mu) % n_shards != 0:
        mu -= 1
    knobs["microbatch"] = mu
    return knobs


# ---------------------------------------------------------------------------
# batch construction
# ---------------------------------------------------------------------------

def _i32(shape):  # tokens / labels
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _f32(shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def batch_specs(cfg: ModelConfig, b: int, s: int) -> dict:
    """Abstract training/prefill batch for one global step."""
    if cfg.frontend is not None and cfg.frontend.kind == "audio_frames":
        return {"embeds": _f32((b, s, cfg.frontend.input_dim)),
                "labels": _i32((b, s))}
    if cfg.frontend is not None and cfg.frontend.kind == "vit_patches":
        n_p = cfg.frontend.n_positions
        s_txt = max(s - n_p, 8)
        return {"embeds": _f32((b, n_p, cfg.frontend.input_dim)),
                "tokens": _i32((b, s_txt)),
                "labels": _i32((b, s_txt))}
    return {"tokens": _i32((b, s)), "labels": _i32((b, s))}


def batch_shardings(dist: DistContext, batch: dict, b: int) -> dict:
    return {k: dist.named(dist.batch_pspec(v.ndim, b))
            for k, v in batch.items()}


# ---------------------------------------------------------------------------
# cache shardings
# ---------------------------------------------------------------------------

def cache_sharding_tree(dist: DistContext, cfg: ModelConfig,
                        shapes: Any, batch: int) -> Any:
    """Shard caches: batch over data axes (dim 1 under stacked 'periods',
    dim 0 under 'tail'); kv-head dims over model when divisible, otherwise
    the cache *sequence* dim is sharded over model (a 32k×128 GQA cache with
    8 kv-heads would otherwise replicate 16× over the model axis and blow the
    HBM budget — caught by the dry-run memory analysis)."""
    tp = dist.tp_axis

    def one(path, sds):
        keys = [getattr(p, "key", None) for p in path]
        stacked = "periods" in keys
        bdim = 1 if stacked else 0
        shape = sds.shape
        spec: list = [None] * len(shape)
        from repro.sharding.rules import batch_spec as _bs
        bs = _bs(1, dist.batch_axes, shape[bdim], dist.mesh)[0]
        spec[bdim] = bs
        is_kv = len(shape) >= 4 and keys[-1] in ("k", "v")
        is_mla = keys[-1] in ("c_kv", "k_rope") and len(shape) >= 3
        if is_kv and cfg.n_kv_heads and shape[-2] == cfg.n_kv_heads:
            if cfg.n_kv_heads % dist.tp_size == 0:
                spec[-2] = tp
            elif shape[-3] % dist.tp_size == 0:  # seq dim
                spec[-3] = tp
        elif is_mla and shape[bdim + 1] % dist.tp_size == 0:
            spec[bdim + 1] = tp  # MLA latent cache: seq over model
        return dist.named(P(*spec))

    return jax.tree_util.tree_map_with_path(one, shapes)


def decode_cache_shapes(cfg: ModelConfig, batch: int, max_len: int) -> Any:
    return jax.eval_shape(
        lambda: init_caches(cfg, batch, max_len, jnp.dtype(cfg.dtype)))


# ---------------------------------------------------------------------------
# the cells
# ---------------------------------------------------------------------------


def reduced_depth(cfg: ModelConfig, n_periods: int) -> ModelConfig:
    """Same arch at ``n_periods`` scan periods (leading dense and remainder
    layers preserved) —
    the costing-compile trick: FLOPs/bytes/collectives are *exactly* linear in
    the period count, so two shallow unrolled compiles extrapolate to full
    depth (XLA's cost_analysis counts while bodies once; see dryrun.py)."""
    return cfg.with_(n_layers=cfg.n_dense_layers + cfg.period * n_periods
                     + cfg.n_remainder)


def make_cell(arch: str, shape: Shape, dist: DistContext, *,
              overrides: dict | None = None,
              costing_periods: int | None = None) -> CellSpec:
    """``costing_periods``: build the reduced-depth, fully-unrolled costing
    variant instead of the deliverable rolled-scan program. For train cells
    the costing program is value_and_grad of ONE microbatch (the per-step
    totals are reassembled in dryrun.py as µ × fb + analytic optimizer)."""
    cfg = get_config(arch)
    overrides = dict(overrides or {})
    # generic ModelConfig knob overrides (hillclimb variants)
    for key in ("score_dtype", "kv_chunk"):
        if key in overrides:
            cfg = cfg.with_(**{key: overrides.pop(key)})
    b, s = shape.global_batch, shape.seq_len
    costing = costing_periods is not None
    if costing:
        cfg = reduced_depth(cfg, costing_periods)
    unroll = True if costing else 1

    if shape.step == "train":
        ocfg = optimizer_for(cfg)
        knobs = resolve_knobs(cfg, dist, b, overrides)
        if costing:
            import jax as _jax
            mb = max(1, knobs.get("microbatch") or 1)
            b_mb = max(b // mb, 1)
            batch = batch_specs(cfg, b_mb, s)
            batch_sh = batch_shardings(dist, batch, b_mb)
            from repro.models.params import param_shapes as pshapes
            from repro.models.transformer import model_spec as mspec
            from repro.sharding.state import params_axes as paxes
            p_shapes = pshapes(mspec(cfg), jnp.dtype(cfg.dtype))
            p_sh = dist.param_shardings(p_shapes, paxes(cfg))
            from repro.train.step import _loss_fn
            aux_w = (cfg.moe.router_aux_weight if cfg.moe is not None
                     else 0.0)

            def fb(params, bt):
                (loss, m), g = _jax.value_and_grad(
                    lambda p: _loss_fn(p, cfg, bt, dist, knobs["remat"],
                                       aux_w, True),
                    has_aux=True)(params)
                return loss, g

            return CellSpec(fn=fb, args=(p_shapes, batch),
                            in_shardings=(p_sh, batch_sh),
                            out_shardings=None,
                            static_notes={"step": "train-fb",
                                          "microbatch": mb})
        state_shapes = train_state_shapes(cfg, ocfg)
        st_axes = state_axes(cfg, ocfg)
        state_sh = dist.param_shardings(state_shapes, st_axes)
        batch = batch_specs(cfg, b, s)
        batch_sh = batch_shardings(dist, batch, b)
        fn = make_train_step(cfg, ocfg, dist=dist, unroll=unroll, **knobs)
        return CellSpec(
            fn=fn, args=(state_shapes, batch),
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
            static_notes={"knobs": knobs, "step": "train"},
        )

    # inference cells share abstract params (no optimizer)
    from repro.models.params import param_shapes as pshapes
    from repro.models.transformer import model_spec
    from repro.sharding.state import params_axes
    p_shapes = pshapes(model_spec(cfg), jnp.dtype(cfg.dtype))
    p_sh = dist.param_shardings(p_shapes, params_axes(cfg))

    if shape.step == "prefill":
        batch = batch_specs(cfg, b, s)
        batch_sh = batch_shardings(dist, batch, b)
        fn = make_prefill_step(cfg, dist=dist, unroll=unroll)
        if cfg.encoder_only:
            return CellSpec(fn=fn, args=(p_shapes, batch),
                            in_shardings=(p_sh, batch_sh),
                            out_shardings=None,
                            static_notes={"step": "prefill"})
        batch.pop("labels", None)
        batch_sh.pop("labels", None)
        caches = decode_cache_shapes(cfg, b, s)
        caches_sh = cache_sharding_tree(dist, cfg, caches, b)
        return CellSpec(fn=fn, args=(p_shapes, batch, caches),
                        in_shardings=(p_sh, batch_sh, caches_sh),
                        out_shardings=(None, caches_sh),
                        donate_argnums=() if costing else (2,),
                        static_notes={"step": "prefill"})

    # decode: one new token against a seq_len cache
    caches = decode_cache_shapes(cfg, b, s)
    caches_sh = cache_sharding_tree(dist, cfg, caches, b)
    tokens = _i32((b, 1))
    tokens_sh = dist.named(dist.batch_pspec(2, b))
    idx = jax.ShapeDtypeStruct((), jnp.int32)
    idx_sh = dist.named(P())
    fn = make_serve_step(cfg, dist=dist, unroll=unroll)
    return CellSpec(fn=fn, args=(p_shapes, tokens, caches, idx),
                    in_shardings=(p_sh, tokens_sh, caches_sh, idx_sh),
                    out_shardings=(None, None, caches_sh),
                    donate_argnums=() if costing else (2,),
                    static_notes={"step": "decode"})
