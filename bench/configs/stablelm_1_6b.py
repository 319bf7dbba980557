"""Plain reference for the ``stablelm_1_6b`` configuration as it is run.

A dense decoder written out in ``jax.numpy`` with nothing of the program
imported: token embedding, ``n_layers`` pre-norm blocks (RMSNorm,
multi-head attention with rotate-half RoPE over the whole head, causal
softmax, a SiLU-gated MLP), a final RMSNorm and the unembedding. The
weights are the benchmark's own, drawn here from the seed in the serving
dtype; :func:`program_params` only renames them into the program's
parameter tree.

``logits_at`` is the float32 reference (matmuls at ``highest``
precision). ``control_top`` is the control: the same network with every
matmul weight rounded to float8 e4m3 (one scale per output channel) and
bfloat16 activations, the step below the configuration's bfloat16.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F8_MAX = 448.0


def _dims(c: dict):
    return (c["n_layers"], c["d_model"], c["n_heads"], c["n_kv_heads"],
            c["head_dim"], c["d_ff"], -(-c["vocab_size"] // 256) * 256)


def init_weights(key, c: dict) -> dict:
    """Seeded weights in ``c["dtype"]``: unit-variance fan-in scaling per
    matmul, 0.02 for the embedding table, ones for the norm scales."""
    nl, d, h, kv, dh, f, vp = _dims(c)
    dt = jnp.dtype(c["dtype"])
    ks = iter(jax.random.split(key, 9))

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32) * std
                ).astype(dt)

    return {
        "embedding": normal((vp, d), 0.02),
        "unembed": normal((d, vp), 1 / math.sqrt(d)),
        "final_norm": jnp.ones((d,), dt),
        "norm1": jnp.ones((nl, d), dt),
        "norm2": jnp.ones((nl, d), dt),
        "wq": normal((nl, d, h, dh), 1 / math.sqrt(d)),
        "wk": normal((nl, d, kv, dh), 1 / math.sqrt(d)),
        "wv": normal((nl, d, kv, dh), 1 / math.sqrt(d)),
        "wo": normal((nl, h, dh, d), 1 / math.sqrt(h * dh)),
        "w_gate": normal((nl, d, f), 1 / math.sqrt(d)),
        "w_up": normal((nl, d, f), 1 / math.sqrt(d)),
        "w_down": normal((nl, f, d), 1 / math.sqrt(f)),
    }


def program_params(w: dict) -> dict:
    """The same arrays under the program's names (one scanned period of
    one attention block)."""
    return {
        "embed": {"embedding": w["embedding"], "unembed": w["unembed"]},
        "final_norm": {"scale": w["final_norm"]},
        "periods": {"0": {
            "norm1": {"scale": w["norm1"]},
            "mix": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                    "wo": w["wo"]},
            "norm2": {"scale": w["norm2"]},
            "ffn": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                    "w_down": w["w_down"]}}},
    }


def _fp8(w, axes):
    """Round to float8 e4m3 with one scale per output channel (the
    reduction ``axes`` are the input ones), back in bfloat16."""
    w = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axes, keepdims=True),
                        1e-12) / F8_MAX
    q = (w / scale).astype(jnp.float8_e4m3fn)
    return (q.astype(jnp.float32) * scale).astype(jnp.bfloat16)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs        # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _hidden(w, tokens, c, low: bool):
    """Final-norm hidden states (B, S, d) in float32. ``low``: the control
    (fp8 weights, bfloat16 activations)."""
    nl, d, h, kv, dh, f, vp = _dims(c)
    act = jnp.bfloat16 if low else jnp.float32

    def mat(x, wt, axes):
        wt = _fp8(wt, axes) if low else wt.astype(jnp.float32)
        return x.astype(act), wt.astype(act)

    s = tokens.shape[1]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    x = w["embedding"][tokens].astype(jnp.float32)

    def block(x, lw):
        hn = _rms(x, lw["norm1"], c["rms_eps"])
        a, wq = mat(hn, lw["wq"], (0,))
        q = jnp.einsum("bsd,dhk->bshk", a, wq,
                       preferred_element_type=jnp.float32)
        a, wk = mat(hn, lw["wk"], (0,))
        k = jnp.einsum("bsd,dhk->bshk", a, wk,
                       preferred_element_type=jnp.float32)
        a, wv = mat(hn, lw["wv"], (0,))
        v = jnp.einsum("bsd,dhk->bshk", a, wv,
                       preferred_element_type=jnp.float32)
        q = _rope(q, pos, c["rope_theta"]).astype(act)
        k = _rope(k, pos, c["rope_theta"]).astype(act)
        g = h // kv
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v.astype(act), g, axis=2)
        sc = jnp.einsum("bqhk,bshk->bhqs", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(dh)
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bhqs,bshk->bqhk", p.astype(act), v,
                       preferred_element_type=jnp.float32)
        a, wo = mat(o, lw["wo"], (0, 1))
        x = x + jnp.einsum("bqhk,hkd->bqd", a, wo,
                           preferred_element_type=jnp.float32)
        hn = _rms(x, lw["norm2"], c["rms_eps"])
        a, wg = mat(hn, lw["w_gate"], (0,))
        gate = jnp.einsum("bsd,df->bsf", a, wg,
                          preferred_element_type=jnp.float32)
        a, wu = mat(hn, lw["w_up"], (0,))
        up = jnp.einsum("bsd,df->bsf", a, wu,
                        preferred_element_type=jnp.float32)
        a, wd = mat(jax.nn.silu(gate) * up, lw["w_down"], (0,))
        x = x + jnp.einsum("bsf,fd->bsd", a, wd,
                           preferred_element_type=jnp.float32)
        return x, None

    layers = {k: w[k] for k in ("norm1", "norm2", "wq", "wk", "wv", "wo",
                                "w_gate", "w_up", "w_down")}
    x, _ = jax.lax.scan(block, x, layers)
    return _rms(x, w["final_norm"], c["rms_eps"])


def _logits(w, hid, c, low: bool):
    vp = _dims(c)[-1]
    wu = _fp8(w["unembed"], (0,)) if low else w["unembed"].astype(
        jnp.float32)
    act = jnp.bfloat16 if low else jnp.float32
    lg = jnp.einsum("btd,dv->btv", hid.astype(act), wu.astype(act),
                    preferred_element_type=jnp.float32)
    return jnp.where(jnp.arange(vp) < c["vocab_size"], lg, -jnp.inf)


def gaps(w, tokens, at, served, c):
    """Float32 reference over ``tokens`` (B, S). At positions ``at``
    (B, T) returns how far the logit of ``served`` (B, T) lies below the
    reference's best logit there."""
    with jax.default_matmul_precision("highest"):
        hid = _hidden(w, tokens, c, low=False)
        hid = jnp.take_along_axis(hid, at[..., None], axis=1)
        lg = _logits(w, hid, c, low=False)
    got = jnp.take_along_axis(lg, served[..., None], axis=2)[..., 0]
    return lg.max(-1) - got


def control_top(w, tokens, at, c):
    """The control's first token at positions ``at`` (B, T)."""
    hid = _hidden(w, tokens, c, low=True)
    hid = jnp.take_along_axis(hid, at[..., None], axis=1)
    return jnp.argmax(_logits(w, hid, c, low=True), axis=-1)
