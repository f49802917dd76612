"""Grouping / aggregation — including delayed aggregation (paper C5, from Mesorasi [8]).

Standard PointNet++ set-abstraction dataflow:
    group:   (M, nsample) idx -> neighbour features (M, nsample, C)
    mlp:     per *grouped* point                    (M, nsample, C')
    pool:    max over nsample                       (M, C')
MLP cost scales with M * nsample — neighbourhoods overlap, so each point is
pushed through the MLP many times.

Delayed aggregation reorders to:
    mlp:     per *point*                            (N, C')
    group:   gather                                 (M, nsample, C')
    pool:    max                                    (M, C')
MLP cost scales with N (each point computed once).  Only the final maxpool
sees grouped data.  Exactness: for the linear part of an MLP layer,
max-pool(linear(x)) == linear applied before grouping; with nonlinearities
it is the Mesorasi approximation, which PointNet++-style nets tolerate
(paper adopts it wholesale — we follow, and quantify in benchmarks).
"""

from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp

from repro.core.query import NeighborSet

_NEG = -1e30


def group_features(features: jax.Array, nbrs: NeighborSet) -> jax.Array:
    """Gather neighbour features: (N, C), (M, nsample) -> (M, nsample, C)."""
    return jnp.take(features, nbrs.idx, axis=0)


def group_relative_coords(
    xyz: jax.Array, centroids_xyz: jax.Array, nbrs: NeighborSet
) -> jax.Array:
    """Neighbour coords relative to their centroid: (M, nsample, 3)."""
    g = jnp.take(xyz, nbrs.idx, axis=0)
    return g - centroids_xyz[:, None, :]


def masked_maxpool(grouped: jax.Array, mask: jax.Array) -> jax.Array:
    """Max over the nsample axis, ignoring padded slots.  (M, S, C) -> (M, C)."""
    neg = jnp.asarray(_NEG, grouped.dtype)
    x = jnp.where(mask[..., None], grouped, neg)
    out = jnp.max(x, axis=-2)
    # centroids with zero neighbours -> 0 features
    any_valid = jnp.any(mask, axis=-1)[..., None]
    return jnp.where(any_valid, out, jnp.zeros_like(out))


def aggregate_standard(features, nbrs, mlp_fn):
    """group -> mlp -> pool (the un-delayed baseline)."""
    grouped = group_features(features, nbrs)  # (M, S, C)
    out = mlp_fn(grouped)  # (M, S, C')
    return masked_maxpool(out, nbrs.mask)


def aggregate_delayed(features, nbrs, mlp_fn):
    """mlp -> group -> pool (paper C5)."""
    pointwise = mlp_fn(features)  # (N, C')
    grouped = group_features(pointwise, nbrs)  # (M, S, C')
    return masked_maxpool(grouped, nbrs.mask)


def interpolate_features(features: jax.Array, idx: jax.Array, weights: jax.Array) -> jax.Array:
    """3-NN inverse-distance interpolation (FP layer up-sampling).

    features: (N, C) at the coarse level; idx/weights: (M, k) -> (M, C).
    The k terms are summed in index order, as the weights are
    (`query.three_nn_interpolate_weights`), so the bits do not depend on
    how the compiler fuses the sum.
    """
    gathered = jnp.take(features, idx, axis=0) * weights[..., None]  # (M, k, C)
    return functools.reduce(operator.add, [gathered[:, j] for j in range(gathered.shape[1])])
