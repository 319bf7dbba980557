"""DistContext — everything the model/step builders need to emit a sharded
program: activation constraints, the expert-parallel MoE island, and the
vocab-parallel (Megatron-style) cross-entropy island.

Design (DESIGN.md §5): GSPMD (pjit + with_sharding_constraint) is the global
strategy — FSDP/ZeRO-3 parameter sharding over ``(pod, data)``, tensor
parallelism over ``model`` — with two explicit ``shard_map`` islands where
GSPMD's inferred collectives would be wrong or wasteful:

* **MoE island**: experts live on the ``model`` axis; activations arrive
  replicated over ``model`` (they are, after the attention psum), every rank
  routes all of its data-shard's tokens, computes its local experts, and one
  ``psum`` combines — the same collective footprint as a dense TP FFN, with
  no (T, E, C) one-hot and no all-to-all. Expert weights are FSDP-gathered
  inside the island (manual ZeRO-3; the backward all-gather→reduce-scatter
  transposition is automatic).
* **CE island**: logits stay vocab-sharded; per-shard logsumexp and the
  label-hit logit are psum'd, so the full (B, S, V) logits never materialize
  replicated.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from repro.models.config import ModelConfig
from repro.models.moe import moe_capacity, shared_expert
from .rules import batch_spec, resolve_spec, tree_shardings


@dataclass(frozen=True)
class DistContext:
    mesh: Mesh
    tp_axis: str = "model"
    # opt-in beyond-baseline optimizations (§Perf hillclimbs):
    #   "flash_decode" — sequence-parallel decode attention island (partial
    #                    softmax merge via psum instead of cache all-gather),
    #   "chunked_ce"   — fused unembed+CE island, scanned over token chunks
    #                    (full fp32 logits never materialize),
    #   "fp8_gather"   — FSDP expert-weight gathers in float8_e4m3.
    flags: frozenset = frozenset()

    def has(self, flag: str) -> bool:
        return flag in self.flags

    @property
    def fsdp_axes(self) -> tuple[str, ...]:
        return tuple(n for n in self.mesh.axis_names if n != self.tp_axis)

    @property
    def batch_axes(self) -> tuple[str, ...]:
        return self.fsdp_axes

    @property
    def tp_size(self) -> int:
        return int(self.mesh.shape[self.tp_axis])

    @property
    def n_devices(self) -> int:
        return int(self.mesh.size)

    # -- spec helpers ---------------------------------------------------------

    def named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def batch_pspec(self, ndim: int, batch_size: int) -> P:
        return batch_spec(ndim, self.batch_axes, batch_size, self.mesh)

    def param_shardings(self, shapes_tree: Any, axes_tree: Any) -> Any:
        return tree_shardings(shapes_tree, axes_tree, self.mesh,
                              fsdp_axes=self.fsdp_axes, tp_axis=self.tp_axis)

    # -- activation constraint ---------------------------------------------------

    def constrain_activation(self, x: jax.Array) -> jax.Array:
        """(B, S, d) activations: batch over data axes, replicated elsewhere."""
        spec = self.batch_pspec(x.ndim, x.shape[0])
        return jax.lax.with_sharding_constraint(x, self.named(spec))

    # -- MoE island ------------------------------------------------------------------

    def moe_island(self, params: dict, cfg: ModelConfig, x: jax.Array, *,
                   decode: bool = False) -> tuple[jax.Array, jax.Array]:
        """x: (B, S, d) -> (y, aux). Experts sharded over ``model``."""
        e = cfg.moe
        tp, fsdp = self.tp_axis, self.fsdp_axes
        if e.n_experts % self.tp_size == 0:
            n_local = e.n_experts // self.tp_size
            expert_sh = tp
        else:  # tiny smoke meshes: replicate experts
            n_local = e.n_experts
            expert_sh = None
        b, s, d = x.shape
        bspec = self.batch_pspec(3, b)
        bax = bspec[0]
        # expert weights: (E, d, f) — E over model, d over fsdp (if divisible)
        d_sh = fsdp if d % _size(self.mesh, fsdp) == 0 else None
        if d_sh is not None and len(d_sh) == 1:
            d_sh = d_sh[0]
        w_spec = P(expert_sh, d_sh, None)
        capacity = None
        tokens_local = (b * s) // _size(self.mesh, _axes_of(bspec[0]))
        if decode:
            capacity = tokens_local
        else:
            capacity = max(1, -(-int(e.top_k * tokens_local *
                                     e.capacity_factor) // e.n_experts))

        if decode and self.has("weight_stationary"):
            return self._moe_ws_island(params, cfg, x, n_local=n_local,
                                       expert_sh=expert_sh, d_sh=d_sh,
                                       capacity=capacity, bax=bax)
        fp8 = self.has("fp8_gather")

        def gathered(w, axis):
            if fp8:
                # fp8 weight gather (DeepSeek-V3 trains in fp8): halves FSDP
                # gather bytes; the transpose reduce-scatter of grads is then
                # also fp8 — acceptable for expert weights per DSv3, noted in
                # EXPERIMENTS.md §Perf.
                w8 = w.astype(jnp.float8_e4m3fn)
                return jax.lax.all_gather(w8, fsdp, axis=axis,
                                          tiled=True).astype(w.dtype)
            return jax.lax.all_gather(w, fsdp, axis=axis, tiled=True)

        def island(route, w_gate, w_up, w_down, xl):
            if d_sh is not None:
                w_gate = gathered(w_gate, 1)
                w_up = gathered(w_up, 1)
                w_down_g = gathered(w_down, 2)
            else:
                w_down_g = w_down
            e0 = (jax.lax.axis_index(tp) * n_local if expert_sh is not None
                  else 0)
            flat = xl.reshape(-1, d)
            y, aux = moe_capacity(
                {**route, "w_gate": w_gate, "w_up": w_up,
                 "w_down": w_down_g}, cfg, flat,
                e0=e0, n_local=n_local, capacity=capacity)
            y = jax.lax.psum(y, tp)
            # aux is invariant over `model` (same router, same tokens on every
            # tp rank); mean over exactly the axes the batch is sharded on.
            if _axes_of(bax):
                aux = jax.lax.pmean(aux, _axes_of(bax))
            return y.reshape(xl.shape), aux

        # w_down: (E, f, d) — d is axis 2
        wd_spec = P(expert_sh, None, d_sh)
        y, aux = shard_map(
            island, mesh=self.mesh,
            in_specs=(P(), w_spec, w_spec, wd_spec, P(bax, None, None)),
            out_specs=(P(bax, None, None), P()),
        )(_route(params), params["w_gate"], params["w_up"],
          params["w_down"], x)
        if e.n_shared:
            y = y + shared_expert(params, cfg, x.reshape(-1, d)).reshape(x.shape)
        return y, aux

    # -- weight-stationary decode MoE --------------------------------------------

    def _moe_ws_island(self, params: dict, cfg: ModelConfig, x: jax.Array, *,
                       n_local: int, expert_sh, d_sh, capacity: int, bax
                       ) -> tuple[jax.Array, jax.Array]:
        """Decode-time MoE that never gathers expert weights: tokens are tiny
        at decode (B ≤ a few hundred), so the island all-gathers the *token*
        activations over the FSDP axes, computes with the local d-slice of
        each expert weight, and psums the (E_local, C, f) partials — per-layer
        traffic drops from O(expert-weight bytes) to O(token-activation
        bytes), a ~40× cut on the 671B decode cell (EXPERIMENTS.md §Perf)."""
        from repro.models.moe import router_topk
        e = cfg.moe
        tp, fsdp = self.tp_axis, self.fsdp_axes
        b, s, d = x.shape
        n_fsdp = _size(self.mesh, fsdp)
        d_local = d // n_fsdp if d_sh is not None else d

        def island(route, w_gate, w_up, w_down, xl):
            # gather all tokens (decode: a few hundred rows) over FSDP axes
            flat = xl.reshape(-1, d)
            xg = (jax.lax.all_gather(flat, fsdp, axis=0, tiled=True)
                  if _axes_of(bax) else flat)
            t_g = xg.shape[0]
            gates, idx, aux = router_topk(route, cfg, xg)
            e0 = (jax.lax.axis_index(tp) * n_local if expert_sh is not None
                  else 0)
            if d_sh is not None:
                di = jnp.zeros((), jnp.int32)
                mul = 1
                for ax in reversed(fsdp):
                    di = di + jax.lax.axis_index(ax) * mul
                    mul *= self.mesh.shape[ax]
                x_slice = jax.lax.dynamic_slice_in_dim(
                    xg, di * d_local, d_local, axis=1)
            else:
                x_slice = xg
            cap = max(capacity, t_g)  # decode: dropless
            # dispatch into (E_local * cap, d_local) buffers
            buf = jnp.zeros((n_local * cap, d_local), x_slice.dtype)
            carry = jnp.zeros((e.n_experts,), jnp.int32)
            slots = []
            for j in range(e.top_k):
                oh = jax.nn.one_hot(idx[:, j], e.n_experts, dtype=jnp.int32)
                within = jnp.cumsum(oh, axis=0) - oh
                pos_j = jnp.sum((within + carry[None, :]) * oh, axis=-1)
                carry = carry + oh.sum(0)
                local_e = idx[:, j] - e0
                ok = (local_e >= 0) & (local_e < n_local) & (pos_j < cap)
                slot = jnp.where(ok, local_e * cap + pos_j, n_local * cap)
                slots.append((slot, ok))
                buf = buf.at[slot].add(
                    x_slice * ok[:, None].astype(x_slice.dtype), mode="drop")
            h = buf.reshape(n_local, cap, d_local)
            # partial contractions over the local d-slice, psum'd over FSDP
            g_p = jnp.einsum("ecd,edf->ecf", h, w_gate.astype(h.dtype))
            u_p = jnp.einsum("ecd,edf->ecf", h, w_up.astype(h.dtype))
            if d_sh is not None:
                g_p = jax.lax.psum(g_p, fsdp)
                u_p = jax.lax.psum(u_p, fsdp)
            act = jax.nn.silu(g_p) * u_p
            out_slice = jnp.einsum("ecf,efd->ecd", act,
                                   w_down.astype(h.dtype))  # (E_l, cap, d_l)
            out_flat = out_slice.reshape(n_local * cap, d_local)
            y = jnp.zeros((t_g, d_local), x_slice.dtype)
            for j, (slot, ok) in enumerate(slots):
                picked = jnp.take(out_flat,
                                  jnp.minimum(slot, n_local * cap - 1),
                                  axis=0)
                w = gates[:, j].astype(y.dtype) * ok.astype(y.dtype)
                y = y + picked * w[:, None]
            # reassemble full-d rows, slice back this rank's tokens
            if d_sh is not None:
                y = jax.lax.all_gather(y, fsdp, axis=1, tiled=True)  # (t_g, d)
            if _axes_of(bax):
                bi = jnp.zeros((), jnp.int32)
                mul = 1
                for ax in reversed(_axes_of(bax)):
                    bi = bi + jax.lax.axis_index(ax) * mul
                    mul *= self.mesh.shape[ax]
                t_loc = flat.shape[0]
                y = jax.lax.dynamic_slice_in_dim(y, bi * t_loc, t_loc, axis=0)
            y = jax.lax.psum(y, tp)
            # aux is numerically identical on every rank (router ran on the
            # gathered token set); pmean just marks it replicated for VMA.
            if _axes_of(bax):
                aux = jax.lax.pmean(aux, _axes_of(bax))
            return y.reshape(xl.shape), aux

        w_spec = P(expert_sh, d_sh, None)
        wd_spec = P(expert_sh, None, d_sh)
        y, aux = shard_map(
            island, mesh=self.mesh,
            in_specs=(P(), w_spec, w_spec, wd_spec, P(bax, None, None)),
            out_specs=(P(bax, None, None), P()),
        )(_route(params), params["w_gate"], params["w_up"],
          params["w_down"], x)
        if cfg.moe.n_shared:
            from repro.models.moe import shared_expert
            y = y + shared_expert(params, cfg,
                                  x.reshape(-1, d)).reshape(x.shape)
        return y, aux

    # -- flash-decode: sequence-parallel attention over a seq-sharded cache ----

    def decode_attention(self, q: jax.Array, k: jax.Array, v: jax.Array,
                         k_positions: jax.Array, k_valid: jax.Array, *,
                         window: int | None = None,
                         kv_chunk: int = 1024,
                         q_offset: jax.Array | int = 0,
                         scale: float | None = None) -> jax.Array:
        """q: (B, 1, H, Dk) replicated over ``model``; k/v: (B, S, K, D*)
        sharded over ``model`` on the sequence dim. Each rank attends over its
        local S/tp cache slice; partial (out, m, l) softmax stats merge with
        one tiny psum — the cache never crosses the interconnect (the
        flash-decode pattern, replacing GSPMD's per-layer cache all-gather).
        """
        from repro.models.attention import chunked_attention
        tp = self.tp_axis
        b = q.shape[0]
        bspec = self.batch_pspec(4, b)
        bax = bspec[0]

        def island(ql, kl, vl, kpos, kval, qoff):
            out, m, l = chunked_attention(
                ql, kl, vl, q_offset=qoff, k_positions=kpos, k_valid=kval,
                causal=True, window=window, kv_chunk=kv_chunk, scale=scale,
                return_stats=True)
            m_g = jax.lax.pmax(m, tp)
            alpha = jnp.exp(m - m_g) * l                     # (B, 1, H)
            l_g = jax.lax.psum(alpha, tp)
            o = jax.lax.psum(out.astype(jnp.float32) * alpha[..., None], tp)
            return (o / jnp.maximum(l_g, 1e-37)[..., None]).astype(q.dtype)

        qoff = (jnp.asarray(q_offset, jnp.int32)
                if not isinstance(q_offset, int) else
                jnp.full((b,), q_offset, jnp.int32))
        if qoff.ndim == 0:
            qoff = jnp.broadcast_to(qoff[None], (b,))
        return shard_map(
            island, mesh=self.mesh,
            in_specs=(P(bax, None, None, None), P(bax, tp, None, None),
                      P(bax, tp, None, None), P(bax, tp), P(bax, tp),
                      P(bax)),
            out_specs=P(bax, None, None, None),
        )(q, k, v, k_positions, k_valid, qoff)

    # -- chunked fused CE: unembed + loss without materializing logits ---------

    def fused_ce(self, hidden: jax.Array, embed_params: dict,
                 tie_embeddings: bool, labels: jax.Array,
                 weights: jax.Array | None = None,
                 z_weight: float = 1e-4, chunk: int = 512
                 ) -> tuple[jax.Array, dict]:
        """hidden: (B, S, d) batch-sharded; unembed weight vocab-sharded.
        Scans token chunks inside the island with remat, so the live logits
        working set is (chunk × V/tp) fp32 instead of (S × V/tp) × ~15 copies
        (measured via memory_analysis bisection — see EXPERIMENTS.md §Perf).
        """
        tp = self.tp_axis
        b, s, d = hidden.shape
        w = (embed_params["embedding"].T if tie_embeddings
             else embed_params["unembed"])
        v = w.shape[-1]
        if v % self.tp_size != 0:
            from repro.train.loss import lm_loss
            from repro.models.layers import unembed as _unembed
            raise ValueError("fused_ce requires vocab divisible by tp")
        bspec = self.batch_pspec(3, b)
        bax = bspec[0]
        if weights is None:
            weights = jnp.ones((b, s), jnp.float32)
        fsdp = self.fsdp_axes
        d_sharded = d % _size(self.mesh, fsdp) == 0

        def island(h, wl, lb, wt):
            if d_sharded:
                wl = jax.lax.all_gather(wl, fsdp, axis=0, tiled=True)
            v_local = wl.shape[-1]
            v0 = jax.lax.axis_index(tp) * v_local
            # token-chunk scan over the flattened local tokens
            hb = h.reshape(-1, d)
            lbf = lb.reshape(-1)
            wtf = wt.reshape(-1).astype(jnp.float32)
            t = hb.shape[0]
            cc = min(chunk, t)
            n = -(-t // cc)
            padt = n * cc - t
            if padt:
                hb = jnp.pad(hb, ((0, padt), (0, 0)))
                lbf = jnp.pad(lbf, (0, padt))
                wtf = jnp.pad(wtf, (0, padt))

            def body(carry, i):
                ce_acc, z_acc = carry
                hc = jax.lax.dynamic_slice_in_dim(hb, i * cc, cc, 0)
                lc = jax.lax.dynamic_slice_in_dim(lbf, i * cc, cc, 0)
                wc = jax.lax.dynamic_slice_in_dim(wtf, i * cc, cc, 0)
                lg = (hc @ wl).astype(jnp.float32)
                m_local = jax.lax.stop_gradient(lg.max(-1))
                m = jax.lax.stop_gradient(jax.lax.pmax(m_local, tp))
                lse = m + jnp.log(jax.lax.psum(
                    jnp.exp(lg - m[:, None]).sum(-1), tp))
                idx = jnp.clip(lc.astype(jnp.int32) - v0, 0, v_local - 1)
                hit = (lc >= v0) & (lc < v0 + v_local)
                ll = jax.lax.psum(
                    jnp.where(hit, jnp.take_along_axis(
                        lg, idx[:, None], axis=-1)[:, 0], 0.0), tp)
                nll = lse - ll
                ce_acc = ce_acc + (nll * wc).sum()
                z_acc = z_acc + (jnp.square(lse) * wc).sum()
                return (ce_acc, z_acc), None

            body = jax.checkpoint(body, prevent_cse=False)
            # initial accumulators must carry the same varying-axes type as
            # the body outputs (they vary per data shard)
            zero = jax.lax.pcast(jnp.zeros((), jnp.float32),
                                 _axes_of(bax), to="varying")
            (ce_sum, z_sum), _ = jax.lax.scan(
                body, (zero, zero), jnp.arange(n, dtype=jnp.int32))
            denom = jnp.maximum(jax.lax.psum(wtf.sum(), bax), 1.0)
            ce = jax.lax.psum(ce_sum, bax) / denom
            z = jax.lax.psum(z_sum, bax) / denom
            return ce, z, denom

        w_spec = P(fsdp if len(fsdp) > 1 else fsdp[0], tp) if d_sharded \
            else P(None, tp)
        ce, z, denom = shard_map(
            island, mesh=self.mesh,
            in_specs=(P(bax, None, None), w_spec, P(bax, None), P(bax, None)),
            out_specs=(P(), P(), P()),
        )(hidden, w, labels, weights)
        loss = ce + z_weight * z
        return loss, {"ce": ce, "z_loss": z, "tokens": denom}

    # -- vocab-parallel CE ---------------------------------------------------------------

    def vocab_parallel_loss(self, logits: jax.Array, labels: jax.Array,
                            weights: jax.Array | None = None,
                            z_weight: float = 1e-4
                            ) -> tuple[jax.Array, dict]:
        """logits: (B, S, V) vocab-sharded over ``model``; labels: (B, S)."""
        b, s, v = logits.shape
        tp = self.tp_axis
        if v % self.tp_size != 0:
            from repro.train.loss import lm_loss
            return lm_loss(logits, labels, weights)
        bspec = self.batch_pspec(3, b)
        bax = bspec[0]
        if weights is None:
            weights = jnp.ones((b, s), jnp.float32)

        def island(lg, lb, wt):
            v_local = lg.shape[-1]
            v0 = jax.lax.axis_index(tp) * v_local
            lg = lg.astype(jnp.float32)
            m_local = lg.max(axis=-1)
            # stabilizer only — gradients cancel analytically, so detach
            # (pmax has no differentiation rule).
            m = jax.lax.stop_gradient(
                jax.lax.pmax(jax.lax.stop_gradient(m_local), tp))
            sumexp = jnp.exp(lg - m[..., None]).sum(-1)
            lse = m + jnp.log(jax.lax.psum(sumexp, tp))
            idx_local = jnp.clip(lb.astype(jnp.int32) - v0, 0, v_local - 1)
            hit = (lb.astype(jnp.int32) >= v0) & \
                  (lb.astype(jnp.int32) < v0 + v_local)
            ll_local = jnp.take_along_axis(lg, idx_local[..., None],
                                           axis=-1)[..., 0]
            ll = jax.lax.psum(jnp.where(hit, ll_local, 0.0), tp)
            nll = lse - ll
            wt = wt.astype(jnp.float32)
            denom = jnp.maximum(jax.lax.psum(wt.sum(), bax), 1.0)
            ce = jax.lax.psum((nll * wt).sum(), bax) / denom
            z = jax.lax.psum((jnp.square(lse) * wt).sum(), bax) / denom
            return ce, z, denom

        ce, z, denom = shard_map(
            island, mesh=self.mesh,
            in_specs=(P(bax, None, tp), P(bax, None), P(bax, None)),
            out_specs=(P(), P(), P()),
        )(logits, labels, weights)
        loss = ce + z_weight * z
        return loss, {"ce": ce, "z_loss": z, "tokens": denom}


def _route(params: dict) -> dict:
    """The router's parameters of an MoE layer: its weight and, where the
    config has one, its score-correction bias. Replicated in the islands."""
    return {k: params[k] for k in ("router", "router_bias") if k in params}


def _size(mesh: Mesh, axes: tuple[str, ...] | str | None) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return int(mesh.shape[axes])
    n = 1
    for a in axes:
        n *= int(mesh.shape[a])
    return n


def _axes_of(entry) -> tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)
