"""Pallas kernel: fused lattice query (C1) — L1 distance + box mask + first-k.

The full (M, P) distance matrix never reaches HBM: per centroid block, the
kernel computes L1 distances into VMEM, thresholds at L = 1.6R, and selects
the FIRST `nsample` in-range indices (PointNet++ semantics) in one pass.
HBM output is just (M, nsample) indices + mask, exactly the paper's
'distances are consumed in-situ by the sorter'.

first-k as dense ops (Mosaic-friendly: no scatter, no prefix sum):
    hits   = d <= L                                   (bc, P)
    idx[s] = min over j of (hits[j] & j > idx[s-1] ? j : P),  idx[-1] = -1
slot s is the smallest hit column past slot s-1's, i.e. the (s+1)-th hit;
once a slot finds nothing (P) every later slot finds nothing too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.fps import axis_distance


def _lattice_kernel(c_ref, p_ref, idx_ref, mask_ref, *, nsample: int, l_range: float):
    """c_ref (bc, 3), p_ref (3, P) -> idx (bc, nsample) int32, mask bool."""
    diffs = [c_ref[:, i : i + 1] - p_ref[i : i + 1, :] for i in range(3)]
    d = axis_distance(*diffs, "l1")  # (bc, P)
    bc, pp = d.shape
    hits = d <= l_range
    lane = jax.lax.broadcasted_iota(jnp.int32, (bc, pp), 1)
    prev = jnp.full((bc,), -1, jnp.int32)
    first = None
    for s in range(nsample):
        prev = jnp.min(jnp.where(hits & (lane > prev[:, None]), lane, pp), axis=1)
        found = prev < pp
        if first is None:  # empty slots repeat the first hit (PointNet++)
            first = jnp.where(found, prev, 0)
        idx_ref[:, s] = jnp.where(found, prev, first).astype(jnp.int32)
        mask_ref[:, s] = found


@functools.partial(
    jax.jit, static_argnames=("nsample", "l_range", "interpret")
)
def lattice_tiles_pallas(
    centroids: jax.Array,
    points: jax.Array,
    *,
    nsample: int,
    l_range: float,
    interpret: bool = False,
):
    """Per-tile lattice query in ONE grid: each program queries one tile's
    centroids against that tile's own points (the MSP-local dataflow).

    centroids (T, K, 3), points (T, 3, P) -> idx (T, K, nsample) int32,
    mask (T, K, nsample) bool.  The tile axis is the pallas grid — the
    PreprocessEngine folds (batch x MSP-tiles) into T, so B clouds run as a
    single launch.  `None` block dims squeeze the tile axis, so the body is
    the exact same `_lattice_kernel` as the flat variant below.
    """
    t, kk, three = centroids.shape
    assert three == 3 and points.shape[0] == t and points.shape[1] == 3
    p = points.shape[2]
    if p % 128 != 0:
        raise ValueError(f"P={p} must be a multiple of 128")

    kernel = functools.partial(_lattice_kernel, nsample=nsample, l_range=l_range)
    return pl.pallas_call(
        kernel,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((None, kk, 3), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, 3, p), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, kk, nsample), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, kk, nsample), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, kk, nsample), jnp.int32),
            jax.ShapeDtypeStruct((t, kk, nsample), jnp.bool_),
        ],
        interpret=interpret,
        name="pc2im_lattice_tiles",
    )(centroids, points)


@functools.partial(
    jax.jit, static_argnames=("nsample", "l_range", "bc", "interpret")
)
def lattice_pallas(
    centroids: jax.Array,
    points: jax.Array,
    *,
    nsample: int,
    l_range: float,
    bc: int = 128,
    interpret: bool = False,
):
    """centroids (M, 3), points (3, P) -> (idx (M,nsample), mask (M,nsample))."""
    m, three = centroids.shape
    assert three == 3 and points.shape[0] == 3
    p = points.shape[1]
    if p % 128 != 0:
        raise ValueError(f"P={p} must be a multiple of 128")
    bc = min(bc, m)
    if m % bc != 0:
        raise ValueError(f"M={m} not divisible by block {bc}")

    kernel = functools.partial(_lattice_kernel, nsample=nsample, l_range=l_range)
    return pl.pallas_call(
        kernel,
        grid=(m // bc,),
        in_specs=[
            pl.BlockSpec((bc, 3), lambda i: (i, 0)),
            pl.BlockSpec((3, p), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bc, nsample), lambda i: (i, 0)),
            pl.BlockSpec((bc, nsample), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, nsample), jnp.int32),
            jax.ShapeDtypeStruct((m, nsample), jnp.bool_),
        ],
        interpret=interpret,
        name="pc2im_lattice_query",
    )(centroids, points)
