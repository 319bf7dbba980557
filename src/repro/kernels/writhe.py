"""Pallas TPU kernel for the Gauss-linking-integral writhe map — the paper's
computational workload, adapted to TPU.

AlphaKnot's pipeline (paper §4) computes topological invariants over protein
backbones; the knot-position heuristic needs per-segment-pair crossing
contributions (the *writhe map* W[i,j]), an O(n²) pairwise computation that
Topoly runs on GPU. The TPU adaptation tiles segment pairs into
(block_i × block_j) VMEM blocks; each grid cell evaluates the Klenin–Langowski
(2000) Gauss integral for its pair block with pure VPU element-wise math —
there is no reduction between blocks, so the grid is fully parallel.

W[i,j] = Ω_ij / 2π, the signed solid angle of segment pair (i, j); the total
writhe of subchain [a, b) is ``W[a:b, a:b].sum()`` — which is exactly what the
knot-core localization scan in ``repro.apps.knots`` consumes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _cross(a, b):
    ax, ay, az = a
    bx, by, bz = b
    return (ay * bz - az * by, az * bx - ax * bz, ax * by - ay * bx)


def _dot(a, b):
    return a[0] * b[0] + a[1] * b[1] + a[2] * b[2]


def _norm(a, eps):
    n = jnp.sqrt(_dot(a, a) + eps)
    return (a[0] / n, a[1] / n, a[2] / n)


def _asin(x):
    """arcsin on [-1, 1] from sqrt and a polynomial (Cephes ``asinf``, peak
    relative error ~2.5e-7 in f32): Mosaic has no lowering for ``asin``.
    |x| <= 1/2 uses the series in x²; above it, the half-angle identity
    asin(a) = π/2 - 2·asin(sqrt((1-a)/2)) keeps the argument small."""
    a = jnp.abs(x)
    big = a > 0.5
    z = jnp.where(big, 0.5 * (1.0 - a), a * a)
    r = jnp.where(big, jnp.sqrt(z), a)
    poly = ((((4.2163199048e-2 * z + 2.4181311049e-2) * z
              + 4.5470025998e-2) * z + 7.4953002686e-2) * z
            + 1.6666752422e-1)
    y = r + r * z * poly
    y = jnp.where(big, 0.5 * jnp.pi - 2.0 * y, y)
    return jnp.where(x < 0, -y, y)


def _writhe_block(p1, p2, q1, q2, eps=1e-12):
    """Signed pair contribution for segment blocks.
    p1/p2: tuple of 3 arrays (bi, 1); q1/q2: (1, bj). Returns (bi, bj)."""
    r13 = tuple(q1[k] - p1[k] for k in range(3))
    r14 = tuple(q2[k] - p1[k] for k in range(3))
    r23 = tuple(q1[k] - p2[k] for k in range(3))
    r24 = tuple(q2[k] - p2[k] for k in range(3))
    n1 = _norm(_cross(r13, r14), eps)
    n2 = _norm(_cross(r14, r24), eps)
    n3 = _norm(_cross(r24, r23), eps)
    n4 = _norm(_cross(r23, r13), eps)

    def asin_clip(x):
        return _asin(jnp.clip(x, -1.0, 1.0))

    omega = (asin_clip(_dot(n1, n2)) + asin_clip(_dot(n2, n3)) +
             asin_clip(_dot(n3, n4)) + asin_clip(_dot(n4, n1)))
    r12 = tuple(p2[k] - p1[k] for k in range(3))
    r34 = tuple(q2[k] - q1[k] for k in range(3))
    sign = jnp.sign(_dot(_cross(r34, r12), r13))
    return omega * sign / (4.0 * jnp.pi) * 2.0


def _writhe_kernel(s1_ref, s2_ref, t1_ref, t2_ref, o_ref, *, block: int):
    """s1/s2: (1, 3, block, 1) segment endpoints of the i block as columns;
    t1/t2: (1, 3, block) endpoints of the j block as rows. Carrying each
    coordinate on its own leading index keeps every block (8, 128)-tiled,
    so the pairwise math is plain (block, 1) x (1, block) broadcasting."""
    bi = pl.program_id(1)
    bj = pl.program_id(2)
    p1 = tuple(s1_ref[0, k] for k in range(3))            # (bi, 1)
    p2 = tuple(s2_ref[0, k] for k in range(3))
    q1 = tuple(t1_ref[0, k:k + 1, :] for k in range(3))   # (1, bj)
    q2 = tuple(t2_ref[0, k:k + 1, :] for k in range(3))
    w = _writhe_block(p1, p2, q1, q2)
    # adjacent/identical segments have no well-defined crossing: zero the
    # |i - j| <= 1 band.
    ii = bi * block + jax.lax.broadcasted_iota(jnp.int32, w.shape, 0)
    jj = bj * block + jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
    w = jnp.where(jnp.abs(ii - jj) <= 1, 0.0, w)
    o_ref[0] = w.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def writhe_map(coords: jax.Array, *, block: int = 128,
               interpret: bool = False) -> jax.Array:
    """coords: (B, n_points, 3) backbone (e.g. Cα trace) ->
    writhe map (B, n_seg, n_seg) with n_seg = n_points - 1 (padded to a
    multiple of ``block``; pad segments are degenerate and contribute 0).
    Compiled for TPU, ``block`` must be a multiple of 128."""
    b, npts, _ = coords.shape
    nseg = npts - 1
    s1 = coords[:, :-1]
    s2 = coords[:, 1:]
    pad = (-nseg) % block
    if pad:
        # repeat the last point: zero-length segments -> zero contribution
        last = s2[:, -1:]
        s1 = jnp.concatenate([s1, jnp.repeat(last, pad, 1)], axis=1)
        s2 = jnp.concatenate([s2, jnp.repeat(last, pad, 1)], axis=1)
    n = s1.shape[1]
    nb = n // block
    r1 = s1.transpose(0, 2, 1)                  # (B, 3, n) rows
    r2 = s2.transpose(0, 2, 1)
    col_spec = pl.BlockSpec((1, 3, block, 1), lambda bi, i, j: (bi, 0, i, 0))
    row_spec = pl.BlockSpec((1, 3, block), lambda bi, i, j: (bi, 0, j))
    out = pl.pallas_call(
        functools.partial(_writhe_kernel, block=block),
        grid=(b, nb, nb),
        in_specs=[col_spec, col_spec, row_spec, row_spec],
        out_specs=pl.BlockSpec((1, block, block),
                               lambda bi, i, j: (bi, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, n, n), jnp.float32),
        interpret=interpret,
    )(r1[..., None], r2[..., None], r1, r2)
    return out[:, :nseg, :nseg]
