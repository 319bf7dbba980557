"""Per-architecture smoke tests: reduced configs of the same family run one
forward + one train step on CPU, asserting shapes and finiteness. Decode
paths are checked for prefill/decode consistency on the families that serve.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, smoke_config
from repro.models import count_params, init_params, model_spec
from repro.models.transformer import forward, init_caches
from repro.optim import OptimizerConfig
from repro.train import init_train_state, make_serve_step, make_train_step


def _smoke_batch(cfg, rng, batch=2, seq=32):
    r = np.random.RandomState(rng)
    out = {}
    if cfg.frontend is not None and cfg.frontend.kind == "audio_frames":
        out["embeds"] = jnp.asarray(
            r.randn(batch, seq, cfg.frontend.input_dim), jnp.float32)
        out["labels"] = jnp.asarray(
            r.randint(0, cfg.vocab_size, (batch, seq)), jnp.int32)
        return out
    if cfg.frontend is not None and cfg.frontend.kind == "vit_patches":
        n_p = cfg.frontend.n_positions
        out["embeds"] = jnp.asarray(
            r.randn(batch, n_p, cfg.frontend.input_dim), jnp.float32)
        out["tokens"] = jnp.asarray(
            r.randint(0, cfg.vocab_size, (batch, seq)), jnp.int32)
        out["labels"] = jnp.asarray(
            r.randint(0, cfg.vocab_size, (batch, seq)), jnp.int32)
        return out
    out["tokens"] = jnp.asarray(
        r.randint(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    out["labels"] = jnp.asarray(
        r.randint(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    return out


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_shapes_and_finite(arch):
    cfg = smoke_config(arch)
    params = init_params(model_spec(cfg), jax.random.PRNGKey(0),
                         jnp.dtype(cfg.dtype))
    batch = _smoke_batch(cfg, 0)
    logits, caches, aux = forward(params, cfg, batch)
    s = batch["labels"].shape[1]
    assert logits.shape == (2, s, cfg.padded_vocab)
    assert caches is None
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step_descends(arch):
    cfg = smoke_config(arch)
    ocfg = OptimizerConfig(lr=1e-2, warmup_steps=0, total_steps=100,
                           schedule="constant", weight_decay=0.0)
    state = init_train_state(cfg, ocfg, jax.random.PRNGKey(1))
    step = jax.jit(make_train_step(cfg, ocfg))
    batch = _smoke_batch(cfg, 1)
    losses = []
    for _ in range(4):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0], losses  # memorizes a fixed batch
    assert int(state.step) == 4


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if a not in ("hubert_xlarge",)])
def test_smoke_decode_matches_prefill(arch):
    """Teacher-forced decode equals the training-forward logits (validates
    caches: KV, ring-buffer local, MLA latent, SSD/RG-LRU state)."""
    cfg = smoke_config(arch)
    if cfg.frontend is not None:
        pytest.skip("vlm decode covered separately")
    params = init_params(model_spec(cfg), jax.random.PRNGKey(2),
                         jnp.dtype(cfg.dtype))
    seq = 48
    batch = _smoke_batch(cfg, 2, seq=seq)
    ref_logits, _, _ = forward(params, cfg, batch)

    caches = init_caches(cfg, 2, seq, jnp.dtype(cfg.dtype))
    serve = jax.jit(make_serve_step(cfg))
    errs = []
    for t in range(seq):
        logits, _, caches = serve(params, batch["tokens"][:, t:t + 1],
                                  caches, jnp.asarray(t, jnp.int32))
        errs.append(float(jnp.abs(logits - ref_logits[:, t]).max()))
    assert max(errs) < 2e-2, max(errs)


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_instantiates_abstractly(arch):
    """The assigned full-size config builds an abstract param tree (no
    allocation) with a sane parameter count."""
    cfg = get_config(arch)
    spec = model_spec(cfg)
    n = count_params(spec)
    expected = {
        "moonlight_16b_a3b": (15e9, 17e9),
        "deepseek_v3_671b": (600e9, 720e9),
        "stablelm_1_6b": (1.2e9, 2.2e9),
        "gemma3_1b": (0.7e9, 1.5e9),
        "internlm2_1_8b": (1.4e9, 2.4e9),
        "gemma3_4b": (3e9, 5.5e9),
        "hubert_xlarge": (0.7e9, 1.3e9),
        "recurrentgemma_2b": (2e9, 3.5e9),
        "internvl2_1b": (0.4e9, 1.0e9),
        "mamba2_130m": (0.1e9, 0.2e9),
    }[arch]
    assert expected[0] < n < expected[1], f"{arch}: {n/1e9:.2f}B params"
    # analytic count from the config agrees with the spec tree
    assert abs(cfg.param_count() - n) / n < 0.05


def test_vlm_prefill_places_patches_before_text():
    cfg = smoke_config("internvl2_1b")
    params = init_params(model_spec(cfg), jax.random.PRNGKey(3),
                         jnp.dtype(cfg.dtype))
    batch = _smoke_batch(cfg, 3, seq=16)
    logits, _, _ = forward(params, cfg, batch)
    # logits cover text positions only
    assert logits.shape == (2, 16, cfg.padded_vocab)
