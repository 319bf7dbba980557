"""Production mesh construction.

Single pod: (data=16, model=16) — 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the ``pod`` axis joins
the FSDP/data-parallel group (gradient all-reduce crosses DCN/pod links,
tensor parallelism never leaves a pod).

Every axis is ``AxisType.Auto``: the model stack is written for GSPMD
(``with_sharding_constraint`` + ``shard_map`` islands), and ``jax.make_mesh``
would otherwise default to explicit-sharding axes.

Defined as functions so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS *before* any jax import; see dryrun.py).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...],
               axes: tuple[str, ...]) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_smoke_mesh(n_data: int = 2, n_model: int = 2,
                    pods: int = 0) -> jax.sharding.Mesh:
    """Small mesh for in-CI island tests (requires host-device override)."""
    if pods:
        return _auto_mesh((pods, n_data, n_model), ("pod", "data", "model"))
    return _auto_mesh((n_data, n_model), ("data", "model"))
