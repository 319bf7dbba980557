"""Mixture-of-Experts FFN with top-k routing, shared experts, and
capacity-bounded dispatch.

Two numerically-matching implementations:

* :func:`moe_ref` — dense reference: every expert computes every token,
  outputs weighted by gates. Exact (dropless); used as the oracle in tests
  and for tiny smoke configs.
* :func:`moe_capacity` — production path: per-shard capacity buffers built by
  a loop-over-k scatter (no ``(T, E, C)`` one-hot tensor is ever
  materialized). This function is written **per-shard**: it computes experts
  ``[e0, e0 + n_local)`` only and returns a *partial* output, so the sharded
  wrapper can run it inside ``shard_map`` with experts on the ``model`` axis
  and ``psum`` the partials (EP with activation replication — the same
  collective footprint as Megatron TP). With ``e0=0, n_local=E`` it is the
  single-device implementation.

Router (fp32): a softmax over the experts, or DeepSeek-V3's independent
sigmoid per expert with a correction bias that only the choice of the top-k
sees; gates are the chosen scores, renormalised over the selected experts
and scaled as the config states; Switch-style load-balancing auxiliary loss.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .params import ParamSpec


def moe_spec(cfg: ModelConfig) -> dict:
    e = cfg.moe
    d = cfg.d_model
    spec = {
        # router stays replicated: it is tiny, read inside the EP island on
        # every rank, and sharding it would force a per-layer gather.
        "router": ParamSpec((d, e.n_experts), (None, None),
                            init="normal", scale=0.02),
        "w_gate": ParamSpec((e.n_experts, d, e.d_expert),
                            ("experts", "embed", "expert_ff"), init="lecun"),
        "w_up": ParamSpec((e.n_experts, d, e.d_expert),
                          ("experts", "embed", "expert_ff"), init="lecun"),
        "w_down": ParamSpec((e.n_experts, e.d_expert, d),
                            ("experts", "expert_ff", "embed"), init="lecun"),
    }
    if e.scoring == "sigmoid":
        spec["router_bias"] = ParamSpec((e.n_experts,), (None,),
                                        init="normal", scale=0.1)
    if e.n_shared:
        f = e.n_shared * e.d_expert
        spec["shared"] = {
            "w_gate": ParamSpec((d, f), ("embed", "ff"), init="lecun"),
            "w_up": ParamSpec((d, f), ("embed", "ff"), init="lecun"),
            "w_down": ParamSpec((f, d), ("ff", "embed"), init="lecun"),
        }
    return spec


def router_topk(params: dict, cfg: ModelConfig, x: jax.Array
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x: (T, d) -> (gates (T, k) f32, idx (T, k) i32, aux_loss scalar).

    ``params``: "router" (d, E) and, with sigmoid scoring, "router_bias"
    (E,), which is added to the scores for the choice alone."""
    e = cfg.moe
    logits = (x.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    if e.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)                         # (T, E)
        choice = scores + params["router_bias"].astype(jnp.float32)
        _, idx = jax.lax.top_k(choice, e.top_k)                 # (T, k)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
        probs = scores / scores.sum(-1, keepdims=True)          # for aux
    else:
        probs = jax.nn.softmax(logits, axis=-1)                 # (T, E)
        gates, idx = jax.lax.top_k(probs, e.top_k)              # (T, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    if e.routed_scaling_factor != 1.0:
        gates = gates * e.routed_scaling_factor
    # Switch load-balance loss: E * sum_e f_e * P_e
    t = x.shape[0]
    counts = jnp.zeros((e.n_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    f_e = counts / jnp.maximum(t * e.top_k, 1)
    p_e = probs.mean(0)
    aux = e.n_experts * jnp.sum(f_e * p_e)
    return gates, idx, aux


def _expert_ffn(w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                h: jax.Array) -> jax.Array:
    """h: (E, C, d) -> (E, C, d), swiglu per expert."""
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, w_gate))
    u = jnp.einsum("ecd,edf->ecf", h, w_up)
    return jnp.einsum("ecf,efd->ecd", g * u, w_down)


def moe_capacity(params: dict, cfg: ModelConfig, x: jax.Array, *,
                 e0: int = 0, n_local: int | None = None,
                 capacity: int | None = None
                 ) -> tuple[jax.Array, jax.Array]:
    """Capacity-bounded top-k MoE over local experts [e0, e0+n_local).

    x: (T, d). Returns (partial_out (T, d), aux_loss). Tokens overflowing an
    expert's capacity are dropped (contribute zero), the standard GShard
    bound; ``capacity_factor`` controls the drop rate.
    """
    e = cfg.moe
    t, d = x.shape
    n_local = e.n_experts if n_local is None else n_local
    if capacity is None:
        capacity = max(1, -(-int(e.top_k * t * e.capacity_factor) // e.n_experts))
    gates, idx, aux = router_topk(params, cfg, x)

    # position-in-expert per (token, choice), built k scatters at a time —
    # memory high-water is (T, E) int32, never (T, E, C).
    buf = jnp.zeros((n_local * capacity, d), x.dtype)
    carry = jnp.zeros((e.n_experts,), jnp.int32)
    slots = []
    for j in range(e.top_k):
        oh = jax.nn.one_hot(idx[:, j], e.n_experts, dtype=jnp.int32)  # (T, E)
        within = jnp.cumsum(oh, axis=0) - oh
        pos_j = jnp.sum((within + carry[None, :]) * oh, axis=-1)      # (T,)
        carry = carry + oh.sum(0)
        local_e = idx[:, j] - e0
        ok = (local_e >= 0) & (local_e < n_local) & (pos_j < capacity)
        slot = jnp.where(ok, local_e * capacity + pos_j, n_local * capacity)
        slots.append((slot, ok))
        buf = buf.at[slot].add(x * ok[:, None].astype(x.dtype),
                               mode="drop")
    h = buf.reshape(n_local, capacity, d)
    w_gate = params["w_gate"]
    w_up = params["w_up"]
    w_down = params["w_down"]
    if w_gate.shape[0] != n_local:  # single-device path slices nothing
        w_gate = jax.lax.dynamic_slice_in_dim(w_gate, e0, n_local, 0)
        w_up = jax.lax.dynamic_slice_in_dim(w_up, e0, n_local, 0)
        w_down = jax.lax.dynamic_slice_in_dim(w_down, e0, n_local, 0)
    out_buf = _expert_ffn(w_gate.astype(x.dtype), w_up.astype(x.dtype),
                          w_down.astype(x.dtype), h)
    out_flat = out_buf.reshape(n_local * capacity, d)
    y = jnp.zeros((t, d), x.dtype)
    for j, (slot, ok) in enumerate(slots):
        picked = jnp.take(out_flat, jnp.minimum(slot, n_local * capacity - 1),
                          axis=0)
        w = gates[:, j].astype(x.dtype) * ok.astype(x.dtype)
        y = y + picked * w[:, None]
    return y, aux


def moe_ref(params: dict, cfg: ModelConfig, x: jax.Array
            ) -> tuple[jax.Array, jax.Array]:
    """Dense dropless reference: all experts on all tokens. x: (T, d)."""
    e = cfg.moe
    gates, idx, aux = router_topk(params, cfg, x)
    g = jax.nn.silu(jnp.einsum("td,edf->tef", x, params["w_gate"].astype(x.dtype)))
    u = jnp.einsum("td,edf->tef", x, params["w_up"].astype(x.dtype))
    per_e = jnp.einsum("tef,efd->ted", g * u, params["w_down"].astype(x.dtype))
    # combine with top-k gates
    weights = jnp.zeros((x.shape[0], e.n_experts), x.dtype)
    for j in range(e.top_k):
        weights = weights.at[jnp.arange(x.shape[0]), idx[:, j]].add(
            gates[:, j].astype(x.dtype))
    y = jnp.einsum("ted,te->td", per_e, weights)
    return y, aux


def shared_expert(params: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Always-on shared expert(s): a plain swiglu FFN (DeepSeek-V3)."""
    p = params["shared"]
    g = jax.nn.silu(x @ p["w_gate"].astype(x.dtype))
    u = x @ p["w_up"].astype(x.dtype)
    return (g * u) @ p["w_down"].astype(x.dtype)


def moe_block(params: dict, cfg: ModelConfig, x: jax.Array, *,
              impl: str = "capacity", e0: int = 0, n_local: int | None = None,
              dropless: bool = False) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out (B, S, d), aux). ``impl``: capacity | ref.
    ``dropless`` sets capacity = n_tokens (used at decode, where token counts
    are tiny and capacity-drops would corrupt generation)."""
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    capacity = b * s if dropless else None
    if impl == "ref":
        y, aux = moe_ref(params, cfg, flat)
    else:
        y, aux = moe_capacity(params, cfg, flat, e0=e0, n_local=n_local,
                              capacity=capacity)
    if cfg.moe.n_shared:
        y = y + shared_expert(params, cfg, flat)
    return y.reshape(b, s, d), aux
