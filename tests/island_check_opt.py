"""Subprocess helper: the OPTIMIZED sharded paths (flash_decode, chunked_ce,
fp8_gather) must match the single-device reference / baseline sharded path."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs import smoke_config
from repro.models import init_params, model_spec
from repro.models.transformer import forward, init_caches
from repro.optim import OptimizerConfig
from repro.sharding import DistContext, state_axes
from repro.train import init_train_state, make_train_step, make_serve_step
from repro.train.step import train_state_shapes
from repro.launch.mesh import make_smoke_mesh


def check_train_chunked_ce(mesh, arch="gemma3_1b"):
    cfg = smoke_config(arch)
    ocfg = OptimizerConfig(lr=1e-2, warmup_steps=0, schedule="constant",
                           weight_decay=0.0)
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 32)), jnp.int32),
             "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 32)), jnp.int32)}
    state = init_train_state(cfg, ocfg, jax.random.PRNGKey(0))
    _, m_ref = jax.jit(make_train_step(cfg, ocfg))(jax.tree.map(jnp.copy, state), batch)

    dist = DistContext(mesh, flags=frozenset({"chunked_ce", "fp8_gather"}))
    st_sh = dist.param_shardings(train_state_shapes(cfg, ocfg), state_axes(cfg, ocfg))
    b_sh = {k: dist.named(dist.batch_pspec(v.ndim, 4)) for k, v in batch.items()}
    with mesh:
        step = jax.jit(make_train_step(cfg, ocfg, dist=dist),
                       in_shardings=(st_sh, b_sh), out_shardings=(st_sh, None))
        _, m_opt = step(jax.device_put(state, st_sh), jax.device_put(batch, b_sh))
    rel = abs(float(m_ref["loss"]) - float(m_opt["loss"])) / abs(float(m_ref["loss"]))
    print(f"chunked_ce {arch}: ref={float(m_ref['loss']):.6f} opt={float(m_opt['loss']):.6f} rel={rel:.2e}")
    assert rel < 2e-3, rel


def check_fp8_gather_moe(mesh):
    cfg = smoke_config("moonlight_16b_a3b")
    ocfg = OptimizerConfig(lr=1e-2, warmup_steps=0, schedule="constant", weight_decay=0.0)
    rng = np.random.RandomState(1)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 32)), jnp.int32),
             "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 32)), jnp.int32)}
    state = init_train_state(cfg, ocfg, jax.random.PRNGKey(1))
    _, m_ref = jax.jit(make_train_step(cfg, ocfg))(jax.tree.map(jnp.copy, state), batch)
    dist = DistContext(mesh, flags=frozenset({"fp8_gather"}))
    st_sh = dist.param_shardings(train_state_shapes(cfg, ocfg), state_axes(cfg, ocfg))
    b_sh = {k: dist.named(dist.batch_pspec(v.ndim, 4)) for k, v in batch.items()}
    with mesh:
        step = jax.jit(make_train_step(cfg, ocfg, dist=dist),
                       in_shardings=(st_sh, b_sh), out_shardings=(st_sh, None))
        _, m_opt = step(jax.device_put(state, st_sh), jax.device_put(batch, b_sh))
    rel = abs(float(m_ref["loss"]) - float(m_opt["loss"])) / abs(float(m_ref["loss"]))
    print(f"fp8_gather moe: ref={float(m_ref['loss']):.6f} opt={float(m_opt['loss']):.6f} rel={rel:.2e}")
    assert rel < 2e-2, rel  # fp8 forward-quantization tolerance


def check_flash_decode(mesh, arch):
    cfg = smoke_config(arch)
    params = init_params(model_spec(cfg), jax.random.PRNGKey(2), jnp.dtype(cfg.dtype))
    rng = np.random.RandomState(2)
    seq = 32
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, seq)), jnp.int32)
    ref_logits, _, _ = forward(params, cfg, {"tokens": tokens})

    dist = DistContext(mesh, flags=frozenset({"flash_decode"}))
    from repro.models.params import param_shapes as pshapes
    from repro.sharding.state import params_axes
    p_sh = dist.param_shardings(pshapes(model_spec(cfg), jnp.dtype(cfg.dtype)),
                                params_axes(cfg))
    from repro.launch.specs import cache_sharding_tree, decode_cache_shapes
    caches = init_caches(cfg, 4, seq, jnp.dtype(cfg.dtype))
    c_sh = cache_sharding_tree(dist, cfg, jax.eval_shape(lambda: caches), 4)
    caches = jax.device_put(caches, c_sh)
    params_d = jax.device_put(params, p_sh)
    with mesh:
        serve = jax.jit(make_serve_step(cfg, dist=dist),
                        in_shardings=(p_sh, dist.named(P("data", None)),
                                      c_sh, dist.named(P())),
                        out_shardings=(None, None, c_sh))
        errs = []
        for t in range(seq):
            logits, _, caches = serve(params_d, tokens[:, t:t+1], caches,
                                      jnp.asarray(t, jnp.int32))
            errs.append(float(jnp.abs(logits[:, :cfg.padded_vocab] - ref_logits[:, t]).max()))
    print(f"flash_decode {arch}: max err {max(errs):.2e}")
    assert max(errs) < 5e-2, max(errs)


def main():
    mesh = make_smoke_mesh(n_data=2, n_model=4)
    check_train_chunked_ce(mesh, "gemma3_1b")
    check_train_chunked_ce(mesh, "stablelm_1_6b")
    check_fp8_gather_moe(mesh)
    check_flash_decode(mesh, "stablelm_1_6b")
    check_flash_decode(mesh, "deepseek_v3_671b")
    check_ws_decode(mesh)
    print("OPT OK")


if __name__ == "__main__":
    main()


def check_ws_decode(mesh):
    """weight_stationary MoE decode must match the baseline decode exactly."""
    from repro.launch.specs import cache_sharding_tree
    cfg = smoke_config("deepseek_v3_671b")
    params = init_params(model_spec(cfg), jax.random.PRNGKey(5), jnp.dtype(cfg.dtype))
    rng = np.random.RandomState(5)
    seq = 24
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, seq)), jnp.int32)
    ref_logits, _, _ = forward(params, cfg, {"tokens": tokens})
    dist = DistContext(mesh, flags=frozenset({"flash_decode",
                                              "weight_stationary"}))
    from repro.models.params import param_shapes as pshapes
    from repro.sharding.state import params_axes
    p_sh = dist.param_shardings(pshapes(model_spec(cfg), jnp.dtype(cfg.dtype)),
                                params_axes(cfg))
    caches = init_caches(cfg, 4, seq, jnp.dtype(cfg.dtype))
    c_sh = cache_sharding_tree(dist, cfg, jax.eval_shape(lambda: caches), 4)
    caches = jax.device_put(caches, c_sh)
    params_d = jax.device_put(params, p_sh)
    with mesh:
        serve = jax.jit(make_serve_step(cfg, dist=dist),
                        in_shardings=(p_sh, dist.named(P("data", None)),
                                      c_sh, dist.named(P())),
                        out_shardings=(None, None, c_sh))
        errs = []
        for t in range(seq):
            logits, _, caches = serve(params_d, tokens[:, t:t+1], caches,
                                      jnp.asarray(t, jnp.int32))
            errs.append(float(jnp.abs(logits - ref_logits[:, t]).max()))
    print(f"weight_stationary decode: max err {max(errs):.2e}")
    assert max(errs) < 5e-2, max(errs)
