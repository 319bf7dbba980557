"""Plain reference for the ``moonlight_16b_a3b`` configuration as it is run.

The DeepSeek-V3 block of Moonlight-16B-A3B written out in ``jax.numpy``
with nothing of the program imported: token embedding, the leading dense
layers (``first_k_dense_replace``), then the expert layers, each a pre-norm
block of multi-head latent attention and a feed-forward; a final RMSNorm
and the unembedding.

- Attention is **materialised** per head: queries by a direct projection
  (``q_lora_rank`` null), keys and values up-projected from the RMS-normed
  latent, one RoPE key (rotate-half) shared by every head, causal softmax
  at scale ``1/sqrt(qk_nope + qk_rope)``.
- The expert layer is **dense** over every expert: sigmoid scores, the
  choice of the top-k by score plus the correction bias (noaux_tc), gates
  the chosen scores renormalised (norm_topk_prob) and scaled, each
  expert's SwiGLU weighted by its gate (zero where not chosen), plus the
  shared experts' SwiGLU.

The weights are the benchmark's own, drawn here from the seed in the
serving dtype, layer by layer, so that no float32 copy of the expert stack
is ever held; :func:`program_params` only renames them into the program's
parameter tree.

``gaps`` is the float32 reference (matmuls at ``highest`` precision), with
the experts computed one at a time and the logits over the served
positions in chunks. It reports at every served position its request's
**mean** gap (the served token's logit below the reference's best,
averaged over the request's served tokens), so the serve driver's widest
reading is the worst request's mean. The widest single gap cannot judge
this model: one bfloat16 rounding swaps a token's sixth expert for a
near-tied seventh in about a third of the positions, and each swap moves
the logits as far as a lower precision does, so the widest gaps of a sound
bfloat16 run and of the float8 control overlap, while their means differ
several times over (PERF.md section 2). ``control_top`` is the control:
the same network with every matmul weight rounded to float8 e4m3 (one
scale per output channel) and bfloat16 activations, the step below the
configuration's bfloat16.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F8_MAX = 448.0
LOGIT_CHUNK = 64          # served positions per block of logits


def _padded_vocab(c: dict) -> int:
    return -(-c["vocab_size"] // 256) * 256


def init_weights(key, c: dict) -> dict:
    """Seeded weights in ``c["dtype"]``: unit-variance fan-in scaling per
    matmul, 0.02 for the embedding table, 0.1 for the router's correction
    bias, ones for the norm scales. ``dense`` is a list of the leading
    dense layers; ``moe`` stacks the expert layers on a leading axis, each
    layer drawn on its own."""
    d, h, vp = c["d_model"], c["n_heads"], _padded_vocab(c)
    m, e = c["mla"], c["moe"]
    n_dense = c["first_k_dense_replace"]
    n_moe = c["n_layers"] - n_dense
    qk = m["nope_head_dim"] + m["rope_head_dim"]
    r = m["kv_lora_rank"]
    nx, f, fs = e["n_experts"], e["d_expert"], e["n_shared"] * e["d_expert"]
    dt = jnp.dtype(c["dtype"])

    def normal(k, shape, std):
        return jax.random.normal(k, shape, dt) * jnp.asarray(std, dt)

    def attn(k):
        ks = jax.random.split(k, 5)
        return {
            "norm1": jnp.ones((d,), dt), "norm2": jnp.ones((d,), dt),
            "kv_norm": jnp.ones((r,), dt),
            "w_q": normal(ks[0], (d, h, qk), 1 / math.sqrt(d)),
            "w_dkv": normal(ks[1], (d, r + m["rope_head_dim"]),
                            1 / math.sqrt(d)),
            "w_uk": normal(ks[2], (r, h, m["nope_head_dim"]),
                           1 / math.sqrt(r)),
            "w_uv": normal(ks[3], (r, h, m["v_head_dim"]), 1 / math.sqrt(r)),
            "w_o": normal(ks[4], (h, m["v_head_dim"], d),
                          1 / math.sqrt(h * m["v_head_dim"])),
        }

    def dense_layer(k):
        ka, kg, ku, kd = jax.random.split(k, 4)
        return {**attn(ka),
                "w_gate": normal(kg, (d, c["d_ff"]), 1 / math.sqrt(d)),
                "w_up": normal(ku, (d, c["d_ff"]), 1 / math.sqrt(d)),
                "w_down": normal(kd, (c["d_ff"], d),
                                 1 / math.sqrt(c["d_ff"]))}

    def moe_layer(k):
        ks = jax.random.split(k, 9)
        return {**attn(ks[0]),
                "router": normal(ks[1], (d, nx), 1 / math.sqrt(d)),
                "router_bias": normal(ks[2], (nx,), 0.1),
                "w_gate": normal(ks[3], (nx, d, f), 1 / math.sqrt(d)),
                "w_up": normal(ks[4], (nx, d, f), 1 / math.sqrt(d)),
                "w_down": normal(ks[5], (nx, f, d), 1 / math.sqrt(f)),
                "s_gate": normal(ks[6], (d, fs), 1 / math.sqrt(d)),
                "s_up": normal(ks[7], (d, fs), 1 / math.sqrt(d)),
                "s_down": normal(ks[8], (fs, d), 1 / math.sqrt(fs))}

    k_emb, k_un, k_dense, k_moe = jax.random.split(key, 4)
    return {
        "embedding": normal(k_emb, (vp, d), 0.02),
        "unembed": normal(k_un, (d, vp), 1 / math.sqrt(d)),
        "final_norm": jnp.ones((d,), dt),
        "dense": [dense_layer(k) for k in jax.random.split(k_dense,
                                                            n_dense)],
        "moe": jax.lax.map(moe_layer, jax.random.split(k_moe, n_moe)),
    }


def program_params(w: dict) -> dict:
    """The same arrays under the program's names: the dense layers under
    ``lead``, the expert layers as one scanned period."""
    def mix(lw):
        return {"w_q": lw["w_q"], "w_dkv": lw["w_dkv"],
                "kv_norm": {"scale": lw["kv_norm"]}, "w_uk": lw["w_uk"],
                "w_uv": lw["w_uv"], "w_o": lw["w_o"]}

    def block(lw, ffn):
        return {"norm1": {"scale": lw["norm1"]}, "mix": mix(lw),
                "norm2": {"scale": lw["norm2"]}, "ffn": ffn}

    mw = w["moe"]
    return {
        "embed": {"embedding": w["embedding"], "unembed": w["unembed"]},
        "final_norm": {"scale": w["final_norm"]},
        "lead": {str(i): block(lw, {"w_gate": lw["w_gate"],
                                    "w_up": lw["w_up"],
                                    "w_down": lw["w_down"]})
                 for i, lw in enumerate(w["dense"])},
        "periods": {"0": block(mw, {
            "router": mw["router"], "router_bias": mw["router_bias"],
            "w_gate": mw["w_gate"], "w_up": mw["w_up"],
            "w_down": mw["w_down"],
            "shared": {"w_gate": mw["s_gate"], "w_up": mw["s_up"],
                       "w_down": mw["s_down"]}})},
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
    """Rotate-half RoPE over the last axis; x: (B, S, ..., D)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs        # (S, half)
    ang = ang.reshape((1, pos.shape[0]) + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


class _Net:
    """The forward pass at one precision: the reference (float32
    weights and activations) or the control (``low``: float8 weights,
    bfloat16 activations)."""

    def __init__(self, c: dict, low: bool):
        self.c, self.low = c, low
        self.act = jnp.bfloat16 if low else jnp.float32

    def dot(self, spec, x, wt, axes):
        """``einsum(spec, x, wt)`` accumulated in float32; ``axes`` are
        the weight's input axes (the control's fp8 scale is per output
        channel)."""
        wt = _fp8(wt, axes) if self.low else wt.astype(jnp.float32)
        return jnp.einsum(spec, x.astype(self.act), wt.astype(self.act),
                          preferred_element_type=jnp.float32)

    def swiglu(self, x, wg, wu, wd):
        g = self.dot("td,df->tf", x, wg, (0,))
        u = self.dot("td,df->tf", x, wu, (0,))
        return self.dot("tf,fd->td", jax.nn.silu(g) * u, wd, (0,))

    def attention(self, x, lw, pos):
        c, m = self.c, self.c["mla"]
        r, nope = m["kv_lora_rank"], m["nope_head_dim"]
        q = self.dot("bsd,dhk->bshk", x, lw["w_q"], (0,))
        q = jnp.concatenate([q[..., :nope],
                             _rope(q[..., nope:], pos, c["rope_theta"])], -1)
        dkv = self.dot("bsd,dr->bsr", x, lw["w_dkv"], (0,))
        lat = _rms(dkv[..., :r], lw["kv_norm"], c["rms_eps"])
        k_rope = _rope(dkv[..., r:], pos, c["rope_theta"])    # (B, S, rope)
        k_nope = self.dot("bsr,rhk->bshk", lat, lw["w_uk"], (0,))
        v = self.dot("bsr,rhk->bshk", lat, lw["w_uv"], (0,))
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope[:, :, None], k_nope.shape[:3] + k_rope.shape[-1:])], -1)
        sc = jnp.einsum("bqhk,bshk->bhqs", q.astype(self.act),
                        k.astype(self.act),
                        preferred_element_type=jnp.float32)
        sc = sc / math.sqrt(q.shape[-1])
        causal = pos[None, :] <= pos[:, None]
        p = jax.nn.softmax(jnp.where(causal[None, None], sc, -jnp.inf), -1)
        o = jnp.einsum("bhqs,bshk->bqhk", p.astype(self.act),
                       v.astype(self.act), preferred_element_type=jnp.float32)
        return self.dot("bqhk,hkd->bqd", o, lw["w_o"], (0, 1))

    def experts(self, x, mw, layer):
        """Routed experts, dense over all of them, plus the shared ones.
        x: (T, d) float32."""
        e = self.c["moe"]
        scores = jax.nn.sigmoid(
            self.dot("td,de->te", x, mw["router"][layer], (0,)))
        choice = scores + mw["router_bias"][layer].astype(jnp.float32)
        _, idx = jax.lax.top_k(choice, e["top_k"])
        gates = jnp.take_along_axis(scores, idx, -1)
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
        gates = gates * e["routed_scaling_factor"]
        t = jnp.arange(x.shape[0])[:, None]
        weight = jnp.zeros_like(scores).at[t, idx].set(gates)   # (T, E)

        def one(i, y):
            out = self.swiglu(x, mw["w_gate"][layer, i],
                              mw["w_up"][layer, i], mw["w_down"][layer, i])
            return y + out * weight[:, i, None]

        y = jax.lax.fori_loop(0, e["n_experts"], one, jnp.zeros_like(x))
        return y + self.swiglu(x, mw["s_gate"][layer], mw["s_up"][layer],
                               mw["s_down"][layer])

    def layers(self, w, tokens):
        """The residual stream after each layer, (B, S, d) float32 each."""
        c = self.c
        b, s = tokens.shape
        pos = jnp.arange(s)
        x = w["embedding"][tokens].astype(jnp.float32)
        out = []

        def block(x, lw, ffn):
            x = x + self.attention(_rms(x, lw["norm1"], c["rms_eps"]), lw,
                                   pos)
            hn = _rms(x, lw["norm2"], c["rms_eps"]).reshape(b * s, -1)
            return x + ffn(hn).reshape(x.shape)

        for lw in w["dense"]:
            x = block(x, lw, lambda hn, lw=lw: self.swiglu(
                hn, lw["w_gate"], lw["w_up"], lw["w_down"]))
            out.append(x)
        mw = w["moe"]
        for i in range(c["n_layers"] - c["first_k_dense_replace"]):
            lw = {k: mw[k][i] for k in ("norm1", "norm2", "kv_norm", "w_q",
                                        "w_dkv", "w_uk", "w_uv", "w_o")}
            x = block(x, lw, lambda hn, i=i: self.experts(hn, mw, i))
            out.append(x)
        return out

    def logits(self, w, hid):
        """hid: (B, T, d) final-normed -> (B, T, padded vocab) float32,
        the padding at -inf."""
        lg = self.dot("btd,dv->btv", hid, w["unembed"], (0,))
        return jnp.where(jnp.arange(lg.shape[-1]) < self.c["vocab_size"],
                         lg, -jnp.inf)

    def at_positions(self, w, tokens, at, fn):
        """``fn(logits, j)`` over the positions ``at`` (B, T), the logits
        made ``LOGIT_CHUNK`` positions at a time; j indexes the chunk's
        positions. Returns (B, T)."""
        hid = _rms(self.layers(w, tokens)[-1], w["final_norm"],
                   self.c["rms_eps"])
        b, t = at.shape
        n = -(-t // LOGIT_CHUNK)
        pad = n * LOGIT_CHUNK - t
        at_p = jnp.pad(at, ((0, 0), (0, pad)))
        hid = jnp.take_along_axis(hid, at_p[..., None], axis=1)

        def chunk(i):
            h = jax.lax.dynamic_slice_in_dim(hid, i * LOGIT_CHUNK,
                                             LOGIT_CHUNK, 1)
            j = i * LOGIT_CHUNK + jnp.arange(LOGIT_CHUNK)
            return fn(self.logits(w, h), j)

        out = jax.lax.map(chunk, jnp.arange(n))          # (n, B, chunk)
        return out.transpose(1, 0, 2).reshape(b, -1)[:, :t]


def hidden_states(w, tokens, c):
    """The float32 reference's residual stream after each layer."""
    with jax.default_matmul_precision("highest"):
        return _Net(c, low=False).layers(w, tokens)


def gaps(w, tokens, at, served, c):
    """Float32 reference over ``tokens`` (B, S). At positions ``at``
    (B, T) takes how far the logit of ``served`` (B, T) lies below the
    reference's best logit there, and returns at each of a request's
    positions the mean of those gaps over the request. A row's served
    positions follow its prompt (``at`` >= 1 past the first); the rest of
    the row is padding, at position 0, and is left out of the mean."""
    served_p = jnp.pad(served, ((0, 0), (0, -served.shape[1] % LOGIT_CHUNK)))

    def gap(lg, j):
        got = jnp.take_along_axis(lg, served_p[:, j][..., None], 2)[..., 0]
        return lg.max(-1) - got

    with jax.default_matmul_precision("highest"):
        g = _Net(c, low=False).at_positions(w, tokens, at, gap)
    live = (jnp.arange(at.shape[1]) == 0)[None] | (at > 0)
    mean = jnp.where(live, g, 0.0).sum(1) / live.sum(1)
    return jnp.broadcast_to(mean[:, None], g.shape)


def control_top(w, tokens, at, c):
    """The control's first token at positions ``at`` (B, T)."""
    return _Net(c, low=True).at_positions(
        w, tokens, at, lambda lg, j: jnp.argmax(lg, -1))
