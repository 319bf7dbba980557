"""Plain reference for the ``alphaknot_screen`` deployment.

The same semantics as the knot campaign, written independently of the
program: the structures and quality scores are regenerated from the ids
(the data, as weights are for a model), the writhe map is a plain
broadcast ``jnp`` Gauss-integral (Klenin-Langowski 1a) in float32, and the
knot core is the greedy subchain shrink over its prefix sums. Nothing here
imports the program.

``dtype`` selects the arithmetic of the writhe map: float32 is the
reference, bfloat16 the control that has to fail the comparison.
"""
from __future__ import annotations

import functools

import numpy as np

QUALITY_SEED = 12345
QUALITY_TABLE = 10_000_000


# -- the data: structures and quality scores, keyed by id -------------------

def _torus_knot(p, q, n, scale=1.0, noise=0.0, seed=0):
    t = np.linspace(0, 2 * np.pi, n, endpoint=False)
    r = np.cos(q * t) + 2.0
    pts = np.stack([r * np.cos(p * t), r * np.sin(p * t),
                    -np.sin(q * t)], -1) * scale
    if noise:
        pts = pts + np.random.RandomState(seed).randn(n, 3) * noise
    return pts.astype(np.float32)


def _random_coil(n, seed=0, drift=(1.0, 0.0, 0.0)):
    rng = np.random.RandomState(seed)
    steps = rng.randn(n, 3)
    steps /= np.linalg.norm(steps, axis=1, keepdims=True)
    steps = steps + np.asarray(drift)
    return np.cumsum(steps * 1.2, axis=0).astype(np.float32)


def _deep_knot(n, core=80, seed=0):
    tre = _torus_knot(2, 3, core, scale=1.2, noise=0.03, seed=seed)
    d = tre[0] - tre.mean(0)
    d = d / (np.linalg.norm(d) + 1e-9) * 5.0
    tail = (n - core) // 2
    head = _random_coil(tail, seed + 1, drift=tuple(d)) + tre[0]
    foot = _random_coil(n - core - tail, seed + 2, drift=tuple(d)) + tre[-1]
    return np.concatenate([head[::-1], tre, foot], 0).astype(np.float32)


def structures(ids, n_points: int) -> np.ndarray:
    """(len(ids), n_points, 3) backbones: id % 4 picks trefoil, random
    coil, cinquefoil or deep trefoil, seeded by the id."""
    out = []
    for i in ids:
        kind = i % 4
        if kind == 0:
            out.append(_torus_knot(2, 3, n_points, noise=0.05, seed=i))
        elif kind == 1:
            out.append(_random_coil(n_points, seed=i))
        elif kind == 2:
            out.append(_torus_knot(2, 5, n_points, noise=0.05, seed=i))
        else:
            out.append(_deep_knot(n_points, core=max(n_points // 2, 48),
                                  seed=i))
    return np.stack(out)


def quality_table() -> np.ndarray:
    """Emulated pLDDT in [0.4, 1.0] for id % 10^7."""
    rng = np.random.RandomState(QUALITY_SEED)
    return (0.4 + 0.6 * rng.random(QUALITY_TABLE)).astype(np.float32)


# -- the invariants -------------------------------------------------------

@functools.lru_cache(maxsize=None)
def writhe_fn(dtype_name: str):
    """Jitted ``coords (B, n, 3) -> (total writhe (B,) f32, map (B, n-1,
    n-1) f32)`` computed in ``dtype_name``."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype_name)

    def fn(coords):
        c = coords.astype(dt)
        p1, p2 = c[:, :-1, None, :], c[:, 1:, None, :]
        q1, q2 = c[:, None, :-1, :], c[:, None, 1:, :]
        r13, r14, r23, r24 = q1 - p1, q2 - p1, q1 - p2, q2 - p2

        def unit(x):
            return x / jnp.sqrt((x * x).sum(-1, keepdims=True)
                                + jnp.asarray(1e-12, dt))

        n1 = unit(jnp.cross(r13, r14))
        n2 = unit(jnp.cross(r14, r24))
        n3 = unit(jnp.cross(r24, r23))
        n4 = unit(jnp.cross(r23, r13))

        def asin_dot(a, b):
            return jnp.arcsin(jnp.clip((a * b).sum(-1), -1.0, 1.0))

        omega = (asin_dot(n1, n2) + asin_dot(n2, n3) + asin_dot(n3, n4)
                 + asin_dot(n4, n1))
        sign = jnp.sign((jnp.cross(q2 - q1, p2 - p1) * r13).sum(-1))
        w = (omega * sign / (2.0 * jnp.pi)).astype(jnp.float32)
        nseg = w.shape[1]
        ii = jnp.arange(nseg)[:, None]
        jj = jnp.arange(nseg)[None, :]
        w = jnp.where(jnp.abs(ii - jj) <= 1, 0.0, w)
        return w.sum(axis=(1, 2)) / 2.0, w

    return jax.jit(fn)


def knot_core(wmap: np.ndarray, threshold: float, min_len: int
              ) -> tuple[tuple[int, int] | None, float]:
    """Greedy shrink of [a, b) from both ends while |writhe(subchain)|
    stays at or above ``threshold``. Returns the core (None when the whole
    chain is under the threshold) and the decision margin: the smallest
    distance of any |writhe| it compared from the threshold."""
    n = wmap.shape[0]
    ps = np.zeros((n + 1, n + 1))
    ps[1:, 1:] = np.cumsum(np.cumsum(wmap.astype(np.float64), 0), 1)
    margin = np.inf

    def above(a, b):
        nonlocal margin
        w = abs((ps[b, b] - ps[a, b] - ps[b, a] + ps[a, a]) / 2.0)
        margin = min(margin, abs(w - threshold))
        return w >= threshold

    a, b = 0, n
    if not above(a, b):
        return None, margin
    changed = True
    while changed and b - a > min_len:
        changed = False
        if above(a + 1, b):
            a += 1
            changed = True
        if b - a > min_len and above(a, b - 1):
            b -= 1
            changed = True
    return (a, b), margin
