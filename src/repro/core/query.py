"""Neighbour queries — ball query (baseline), lattice query (paper C1), kNN-3.

Ball query (PointNet++): the *first* `nsample` points with ||p-c||2 <= R,
padded with the first hit (standard convention).

Lattice query (PC2IM): same first-k semantics but with the L1 (Manhattan)
metric and an adaptive range L = 1.6 * R (paper's empirical factor chosen so
the L1 ball covers the original L2 ball with no explicit information loss —
worst case would need sqrt(3) ~ 1.73).

kNN-3: the 3 nearest neighbours + inverse-distance weights, used by the
point-feature-propagation (up-sampling) layers.
"""

from __future__ import annotations

import functools
import operator
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.fps import Metric, pairwise_distance

LATTICE_RANGE_FACTOR = 1.6  # paper: L = 1.6 * R


class NeighborSet(NamedTuple):
    idx: jax.Array  # (M, nsample) indices into the point set
    mask: jax.Array  # (M, nsample) True where a real (in-range) neighbour


def _first_k_in_range(
    d: jax.Array, thresh: jax.Array | float, nsample: int, valid: jax.Array | None
) -> NeighborSet:
    """First-k selection per row of a distance matrix d: (M, N)."""
    hit = d <= thresh
    if valid is not None:
        hit = hit & valid[None, :]
    # slot for each hit = number of prior hits in the row
    slot = jnp.cumsum(hit, axis=1) - 1  # (M, N)
    rows = jnp.broadcast_to(jnp.arange(d.shape[0])[:, None], d.shape)
    cols = jnp.broadcast_to(jnp.arange(d.shape[1])[None, :], d.shape)
    slot_ok = hit & (slot < nsample)
    out = jnp.zeros((d.shape[0], nsample), jnp.int32)
    msk = jnp.zeros((d.shape[0], nsample), bool)
    out = out.at[jnp.where(slot_ok, rows, d.shape[0]), jnp.where(slot_ok, slot, 0)].set(
        cols.astype(jnp.int32), mode="drop"
    )
    msk = msk.at[jnp.where(slot_ok, rows, d.shape[0]), jnp.where(slot_ok, slot, 0)].set(
        True, mode="drop"
    )
    # pad empty slots with the first hit (PointNet++ convention); if a row has
    # no hit at all, fall back to index 0 (callers aggregate with the mask).
    first = out[:, :1]
    out = jnp.where(msk, out, first)
    return NeighborSet(idx=out, mask=msk)


def ball_query(
    points: jax.Array,
    centroids: jax.Array,
    radius: float,
    nsample: int,
    *,
    valid: jax.Array | None = None,
) -> NeighborSet:
    """L2 ball query.  points: (N,3), centroids: (M,3) -> (M, nsample)."""
    d = pairwise_distance(centroids, points, "l2")  # squared
    return _first_k_in_range(d, radius * radius, nsample, valid)


def lattice_query(
    points: jax.Array,
    centroids: jax.Array,
    radius: float,
    nsample: int,
    *,
    range_factor: float = LATTICE_RANGE_FACTOR,
    valid: jax.Array | None = None,
) -> NeighborSet:
    """PC2IM lattice query: L1 metric, range L = range_factor * radius (C1)."""
    d = pairwise_distance(centroids, points, "l1")
    return _first_k_in_range(d, range_factor * radius, nsample, valid)


def knn(
    query_xyz: jax.Array,
    ref_xyz: jax.Array,
    k: int,
    *,
    metric: Metric = "l2",
    valid: jax.Array | None = None,
):
    """k nearest neighbours of each query point among ref points.

    Returns (idx (M,k) int32, dist (M,k) — squared for l2).  Implemented as
    k successive min-extractions (k is tiny: 3 in PointNet++ FP layers),
    which is exactly the dataflow of the fused kernels/knn3 kernel.
    """
    d = pairwise_distance(query_xyz, ref_xyz, metric)  # (M, N)
    if valid is not None:
        d = jnp.where(valid[None, :], d, jnp.inf)
    idxs, dists = [], []
    for _ in range(k):
        j = jnp.argmin(d, axis=1)
        dj = jnp.take_along_axis(d, j[:, None], axis=1)[:, 0]
        idxs.append(j.astype(jnp.int32))
        dists.append(dj)
        d = d.at[jnp.arange(d.shape[0]), j].set(jnp.inf)
    return jnp.stack(idxs, axis=1), jnp.stack(dists, axis=1)


def three_nn_interpolate_weights(dist_sq: jax.Array, eps: float = 1e-8) -> jax.Array:
    """Inverse-distance weights for 3-NN feature interpolation (FP layer).

    dist_sq: (..., k) — normalised over the trailing k axis, so batched
    (B, M, k) inputs work unchanged.  The k weights are summed in index
    order with elementwise adds: a reduce's association order is the
    compiler's choice, and on the TPU it differed between programs that
    take the distances from the knn3 kernel and from `knn`.
    """
    w = 1.0 / (dist_sq + eps)
    total = functools.reduce(operator.add, [w[..., j : j + 1] for j in range(w.shape[-1])])
    return w / total
