"""Kernel dispatch by platform.

On TPU a kernel asked for runs as compiled Pallas: there is no interpret
mode and no swap to the reference there, so a kernel that the TPU compiler
refuses raises. On other backends the pure-jnp reference runs, or the
Pallas kernel in interpret mode where a test asks for it with
``interpret=True``.
"""
from __future__ import annotations

import jax

from . import ref as _ref
from .writhe import writhe_map


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def writhe(coords, *, block=128, interpret=False):
    if on_tpu() or interpret:
        return writhe_map(coords, block=block, interpret=not on_tpu())
    return _ref.writhe_map_ref(coords)
