"""Sharded-vs-single-device parity of one training step.

The GSPMD program (FSDP over the data axes, tensor parallelism on
``model``, the MoE and vocab-parallel CE ``shard_map`` islands) must compute
the same step as the plain single-device program. :func:`train_step_parity`
runs both from one initial state and batch and reports how far the loss and
the updated parameters are apart. ``tests/island_check.py`` runs it on
virtual host devices; ``chip_smoke.py --chips 4`` on a TPU mesh.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import smoke_config
from repro.optim import OptimizerConfig

from .context import DistContext
from .state import state_axes

# A plain learning-rate step: Adam's first update is lr * g / (|g| + eps),
# which turns float noise in a near-zero gradient into a full ±lr step, so
# eps is set far above that noise and the update stays linear in g.
PARITY_OPT = OptimizerConfig(lr=1e-2, eps=1.0, warmup_steps=0,
                             schedule="constant", weight_decay=0.0)


def parity_batch(cfg, b: int = 4, s: int = 32, seed: int = 0) -> dict:
    """A random batch of the config's input kind (tokens, audio frames or
    image patches + tokens) with labels."""
    rng = np.random.RandomState(seed)

    def ids():
        return jnp.asarray(rng.randint(0, cfg.vocab_size, (b, s)), jnp.int32)

    fe = cfg.frontend
    if fe is not None and fe.kind == "audio_frames":
        return {"embeds": jnp.asarray(rng.randn(b, s, fe.input_dim),
                                      jnp.float32),
                "labels": ids()}
    if fe is not None:
        return {"embeds": jnp.asarray(rng.randn(b, fe.n_positions,
                                                fe.input_dim), jnp.float32),
                "tokens": ids(), "labels": ids()}
    return {"tokens": ids(), "labels": ids()}


def train_step_parity(arch: str, mesh, *, seed: int = 0) -> dict:
    """One ``make_train_step`` step of ``arch``'s smoke config, single
    device vs sharded on ``mesh``. Returns both losses, the loss's relative
    error and ``update_rel``: the L2 norm of the difference of the updated
    parameters over the L2 norm of the single-device update."""
    from repro.train import init_train_state, make_train_step
    from repro.train.step import train_state_shapes

    cfg = smoke_config(arch)
    ocfg = PARITY_OPT
    batch = parity_batch(cfg, seed=seed)
    b = next(iter(batch.values())).shape[0]
    state = init_train_state(cfg, ocfg, jax.random.PRNGKey(seed))

    step_ref = jax.jit(make_train_step(cfg, ocfg))
    new_ref, m_ref = step_ref(jax.tree.map(jnp.copy, state), batch)

    dist = DistContext(mesh)
    state_sh = dist.param_shardings(train_state_shapes(cfg, ocfg),
                                    state_axes(cfg, ocfg))
    batch_sh = {k: dist.named(dist.batch_pspec(v.ndim, b))
                for k, v in batch.items()}
    with mesh:
        step_sh = jax.jit(make_train_step(cfg, ocfg, dist=dist),
                          in_shardings=(state_sh, batch_sh),
                          out_shardings=(state_sh, None))
        new_sh, m_sh = step_sh(jax.device_put(state, state_sh),
                               jax.device_put(batch, batch_sh))

    def sq(tree):
        return sum(float(jnp.sum(jnp.square(x.astype(jnp.float32))))
                   for x in jax.tree.leaves(tree))

    upd = jax.tree.map(lambda n, o: n.astype(jnp.float32) - o, new_ref.params,
                       state.params)
    diff = jax.tree.map(lambda s, r: jax.device_get(s).astype(np.float32)
                        - np.asarray(r, np.float32),
                        new_sh.params, new_ref.params)
    loss_ref, loss_sh = float(m_ref["loss"]), float(m_sh["loss"])
    return {
        "arch": arch,
        "loss_ref": loss_ref,
        "loss_sharded": loss_sh,
        "loss_rel": abs(loss_ref - loss_sh) / max(abs(loss_ref), 1e-9),
        "update_rel": (sq(diff) / max(sq(upd), 1e-30)) ** 0.5,
    }
