"""Moonlight-16B-A3B (the DeepSeek-V3 block): sigmoid routing with a
choice-only bias, the leading dense layer, the paged latent cache and its
decode kernel, against the plain reference in
``bench/configs/moonlight_16b_a3b.py`` at small sizes in float32, on seeded
random weights. Logits are compared, not tokens."""
import dataclasses
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.kernels.flash_decode import (flash_decode_latent,
                                        flash_decode_paged_xla)
from repro.models.config import MLAConfig
from repro.models.layers import embed
from repro.models.moe import router_topk
from repro.models.transformer import block_apply, forward
from repro.serve import ServeEngine

ROOT = Path(__file__).resolve().parents[1]
BENCH_CONFIG = ROOT / "bench" / "configs" / "moonlight_16b_a3b.json"
MOE_KEYS = ("n_experts", "top_k", "d_expert", "n_shared", "scoring",
            "routed_scaling_factor")
MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab_size", "rope_theta", "rms_eps", "dtype")


def _reference():
    spec = importlib.util.spec_from_file_location(
        "moonlight_reference", BENCH_CONFIG.with_suffix(".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _as_dict(cfg) -> dict:
    """The reference's configuration dict for a program config."""
    return {**{k: getattr(cfg, k) for k in MODEL_KEYS},
            "first_k_dense_replace": cfg.n_dense_layers,
            "mla": dataclasses.asdict(cfg.mla),
            "moe": {k: getattr(cfg.moe, k) for k in MOE_KEYS}}


def _program_config(c: dict):
    """The program's config for a reference dict, built as the benchmark's
    serve driver builds it, with the dict's latent and expert dims."""
    cfg = get_config("moonlight_16b_a3b")
    return cfg.with_(**{k: c[k] for k in MODEL_KEYS},
                     n_dense_layers=c["first_k_dense_replace"],
                     mla=MLAConfig(**c["mla"]),
                     moe=dataclasses.replace(cfg.moe, **c["moe"]))


def _bench_reduced() -> dict:
    """The benchmark's configuration file with its widths cut to CPU size
    (every mechanism kept: 1 dense + 2 expert layers, no query LoRA,
    sigmoid routing with a bias, 2 shared experts), in float32."""
    c = json.loads(BENCH_CONFIG.read_text())
    c.update(n_layers=3, d_model=96, n_heads=4, n_kv_heads=4, head_dim=32,
             d_ff=192, vocab_size=512, dtype="float32")
    c["mla"] = dict(c["mla"], kv_lora_rank=32, rope_head_dim=16,
                    nope_head_dim=24, v_head_dim=24)
    c["moe"] = dict(c["moe"], n_experts=8, top_k=3, d_expert=48)
    return c


CONFIGS = {"smoke": _as_dict(smoke_config("moonlight_16b_a3b")),
           "bench_reduced": _bench_reduced()}


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def model(request):
    c = CONFIGS[request.param]
    w = REF.init_weights(jax.random.PRNGKey(7), c)
    # a capacity that drops no token: the reference is dropless, and so
    # is the decode path; the layer-by-layer test runs the training path
    cfg = _program_config(c)
    cfg = cfg.with_(kv_chunk=64, moe=dataclasses.replace(
        cfg.moe, capacity_factor=float(cfg.moe.n_experts)))
    return c, w, cfg, REF.program_params(w)


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------


def test_bias_changes_the_choice_and_never_the_gates():
    """Hand-built scores: with an identity router the logits are the
    tokens' rows, so the scores are their sigmoids. The bias lifts expert
    3 over expert 1 for the choice; the gates stay the chosen experts'
    own scores, renormalised and scaled."""
    base = smoke_config("moonlight_16b_a3b")
    cfg = base.with_(d_model=8, moe=dataclasses.replace(base.moe, top_k=2))
    e = cfg.moe
    logits = jnp.asarray([[2.0, 1.0, -1.0, 0.5, -2.0, -3.0, -1.5, 0.0]])
    router = {"router": jnp.eye(8, dtype=jnp.float32)}
    scores = jax.nn.sigmoid(logits[0])

    def gates_for(idx):
        g = scores[np.asarray(idx)]
        return g / g.sum() * e.routed_scaling_factor

    no_bias = jnp.zeros((8,), jnp.float32)
    g0, i0, _ = router_topk({**router, "router_bias": no_bias}, cfg, logits)
    assert sorted(np.asarray(i0[0]).tolist()) == [0, 1]
    lift = no_bias.at[3].set(0.2)   # 0.622 + 0.2 > 0.731
    g1, i1, _ = router_topk({**router, "router_bias": lift}, cfg, logits)
    assert sorted(np.asarray(i1[0]).tolist()) == [0, 3]
    for g, i in ((g0, i0), (g1, i1)):
        np.testing.assert_allclose(np.asarray(g[0]), gates_for(i[0]),
                                   rtol=1e-6)
    # a bias that keeps the choice keeps the gates bit for bit
    g2, i2, _ = router_topk({**router, "router_bias": no_bias.at[7].set(
        0.1)}, cfg, logits)
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(i0))
    np.testing.assert_array_equal(np.asarray(g2), np.asarray(g0))


def _softmax_router_before(params, cfg, x):
    """``router_topk`` as it stood before sigmoid routing existed."""
    e = cfg.moe
    logits = (x.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, e.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    t = x.shape[0]
    counts = jnp.zeros((e.n_experts,), jnp.float32).at[idx.reshape(-1)].add(
        1.0)
    f_e = counts / jnp.maximum(t * e.top_k, 1)
    aux = e.n_experts * jnp.sum(f_e * probs.mean(0))
    return gates, idx, aux


def test_softmax_routing_is_bit_identical():
    cfg = smoke_config("deepseek_v3_671b")
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((64, cfg.d_model)), jnp.float32)
    params = {"router": jnp.asarray(
        rng.standard_normal((cfg.d_model, cfg.moe.n_experts)) * 0.1,
        jnp.float32)}
    for fn in (lambda p, a: router_topk(p, cfg, a),
               jax.jit(lambda p, a: router_topk(p, cfg, a))):
        got = fn(params, x)
        want = jax.jit(lambda p, a: _softmax_router_before(p, cfg, a))(
            params, x)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# the stack against the reference
# ---------------------------------------------------------------------------


def test_dense_then_moe_stack_matches_reference_layer_by_layer(model):
    """The program's residual stream after the dense layer and after each
    expert layer equals the reference's (materialised attention, dense
    experts). Tolerance 1e-4 of the stream's largest value: float32 on
    both sides, differing only in summation order."""
    c, w, cfg, params = model
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, c["vocab_size"], (2, 20)), jnp.int32)
    want = REF.hidden_states(w, tokens, c)
    x = embed(params["embed"], cfg, tokens)
    got = []
    x, _, _ = block_apply(params["lead"]["0"], cfg, "attn", x, dense=True)
    got.append(x)
    for i in range(cfg.n_periods):
        layer = jax.tree.map(lambda a: a[i], params["periods"]["0"])
        x, _, _ = block_apply(layer, cfg, "attn", x)
        got.append(x)
    assert len(got) == len(want) == c["n_layers"]
    for g, r in zip(got, want):
        scale = float(jnp.abs(r).max())
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=1e-4 * scale)


def _reference_logits(w, c, tokens) -> np.ndarray:
    """The reference's full-sequence logits (B, S, padded vocab)."""
    with jax.default_matmul_precision("highest"):
        hid = REF._rms(REF.hidden_states(w, tokens, c)[-1],
                       w["final_norm"], c["rms_eps"])
        return np.asarray(REF._Net(c, low=False).logits(w, hid))


def _served_logits(eng, reqs):
    """Drive the engine to completion; every step's logits for every
    stepped slot, keyed (request id, position)."""
    for req in reqs:
        assert eng.add_request(*req)
    got, tokens = {}, {}
    while eng._active():
        at = {i: (eng.slots[i].request_id, eng.slots[i].position)
              for i in eng._active()}
        for rid, toks in eng.step():
            tokens[rid] = toks
        for i, key in at.items():
            got[key] = np.asarray(eng.last_logits[i])
    return got, tokens


@pytest.mark.parametrize("kernel", ["chunked", "flash"])
def test_paged_engine_logits_match_reference(model, kernel):
    """Prompts fed and tokens decoded through the paged latent cache
    (the flash kernel in interpret mode): at every position the engine's
    logits equal the reference's full-sequence forward over the same
    tokens. Tolerance 1e-4 of the largest logit: float32 on both sides,
    absorbed against materialised attention and paged online softmax
    against a plain one differ only in summation order."""
    c, w, cfg, params = model
    eng = ServeEngine(cfg, params, n_slots=3, max_len=48, paged=True,
                      page_size=8, decode_kernel=kernel,
                      kernel_interpret=True)
    rng = np.random.default_rng(5)
    reqs = [(f"r{i}", rng.integers(0, c["vocab_size"], 5 + 4 * i).tolist(),
             6 + i) for i in range(3)]
    got, tokens = _served_logits(eng, reqs)
    assert set(tokens) == {rid for rid, _, _ in reqs}
    for rid, prompt, _ in reqs:
        seq = prompt + tokens[rid][:-1]
        want = _reference_logits(w, c, jnp.asarray([seq], jnp.int32))[0]
        v = c["vocab_size"]
        scale = np.abs(want).max()
        for pos in range(len(seq)):
            np.testing.assert_allclose(
                got[(rid, pos)][:v], want[pos, :v], atol=1e-4 * scale,
                err_msg=f"{rid} position {pos}")
    assert eng.allocator.used_pages == 0


# ---------------------------------------------------------------------------
# the latent kernel
# ---------------------------------------------------------------------------


def _latent_oracle(q, stack, layer, qpos, table, dv, scale):
    """Naive per-(slot, head) softmax over the bound positions of the
    slot's pages: K the whole row, V its first ``dv`` lanes."""
    q, stack, table = np.asarray(q), np.asarray(stack), np.asarray(table)
    b, _, h, _ = q.shape
    ps = stack.shape[-2]
    out = np.zeros((b, 1, h, dv), np.float32)
    for bi in range(b):
        rows, pos = [], []
        for li, page in enumerate(table[bi]):
            if page < 0:
                continue
            for off in range(ps):
                if li * ps + off <= qpos[bi]:
                    rows.append(stack[layer, page, 0, off])
                    pos.append(li * ps + off)
        if not rows:
            continue
        kv = np.stack(rows)
        for hi in range(h):
            s = kv @ q[bi, 0, hi] * scale
            p = np.exp(s - s.max())
            out[bi, 0, hi] = (p / p.sum()) @ kv[:, :dv]
    return out


def test_latent_kernel_matches_the_chunked_paged_path():
    """``flash_decode_latent`` (interpret mode) reads one latent head for
    all query heads, K the whole 640-lane row and V its first lanes, from
    a stacked pool with unbound (-1) table entries and a slot with nothing
    bound; the XLA lowering takes the same pool. Both equal a naive
    softmax. Tolerance 2e-5: float32, summation order only."""
    rng = np.random.default_rng(12)
    n_layers, b, h, d, dv, ps, npg = 2, 4, 16, 640, 512, 8, 12
    stack = jnp.asarray(rng.standard_normal((n_layers, npg, 1, ps, d)),
                        jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    qpos = np.asarray([5, 20, 30, 3], np.int32)
    table = jnp.asarray([[3, -1, -1, -1],
                         [7, 2, 9, -1],
                         [1, 4, 11, 10],
                         [-1, -1, -1, -1]], jnp.int32)
    scale = 1 / np.sqrt(192.0)
    for layer in range(n_layers):
        want = _latent_oracle(q, stack, layer, qpos, table, dv, scale)
        pall = flash_decode_latent(q, stack, jnp.asarray(qpos), table,
                                   jnp.int32(layer), dv=dv, scale=scale,
                                   interpret=True)
        xla = flash_decode_paged_xla(q, stack, None, jnp.asarray(qpos),
                                     table, jnp.int32(layer), dv=dv,
                                     scale=scale)
        np.testing.assert_allclose(np.asarray(pall), want, atol=2e-5)
        np.testing.assert_allclose(np.asarray(xla), want, atol=2e-5)
    one = flash_decode_latent(q, stack[1], jnp.asarray(qpos), table, dv=dv,
                              scale=scale, interpret=True)
    np.testing.assert_allclose(np.asarray(one), _latent_oracle(
        q, stack, 1, qpos, table, dv, scale), atol=2e-5)


def test_full_model_forward_runs_through_lead_and_scan():
    """The full forward of the smoke config equals the stack applied
    block by block (lead, then the scanned expert layers)."""
    cfg = smoke_config("moonlight_16b_a3b")
    c = _as_dict(cfg)
    w = REF.init_weights(jax.random.PRNGKey(3), c)
    params = REF.program_params(w)
    tokens = jnp.asarray(np.random.default_rng(2).integers(
        0, cfg.vocab_size, (2, 12)), jnp.int32)
    logits, _, aux = forward(params, cfg, {"tokens": tokens})
    want = _reference_logits(w, c, tokens)
    np.testing.assert_allclose(np.asarray(logits), want,
                               atol=1e-4 * np.abs(want).max())
    assert bool(jnp.isfinite(aux))
