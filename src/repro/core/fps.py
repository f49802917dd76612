"""Farthest Point Sampling (FPS) — exact L2, approximate L1 (paper C1), fused step (C3).

The paper's observation: the FPS inner loop is

    d_tmp  <- min(d_tmp, dist(points, points[last]))     # temporary-distance update
    last   <- argmax(d_tmp)                              # next centroid

Baseline hardware streams `points` and `d_tmp` through memory every
iteration (58% of on-chip traffic is the d_tmp update, 41% the point reads).
PC2IM's APD-CIM + Ping-Pong-MAX CAM keep both pinned next to compute and
fuse the min-update with the max-search.  `fused_fps_step` below is the
software statement of that fusion (a single XLA fusion / one Pallas kernel
in kernels/fps — points and d_tmp stay in VMEM for the whole loop).

Distances:
  * metric="l2"  : squared Euclidean (no sqrt — monotone, what baselines use)
  * metric="l1"  : Manhattan (paper C1).  With 16-bit quantized coordinates
    the L1 distance fits in 19 bits (3 * (2^16 - 1) < 2^18), vs ~33 bits for
    squared L2 — the bit-width saving that shrinks the paper's CAM.
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

Metric = Literal["l1", "l2"]

_BIG = jnp.float32(1e30)


def axis_distance(dx: jax.Array, dy: jax.Array, dz: jax.Array, metric: Metric = "l2") -> jax.Array:
    """Distance from per-axis differences, summed in the fixed order (x + y) + z.

    Every distance in the repo goes through here: the Pallas kernels sum
    coordinate rows and the XLA paths a trailing axis, and spelling the
    order out is what makes their float results bitwise-equal on every
    backend (a reduce over the 3-axis may associate differently).
    """
    if metric == "l1":
        return (jnp.abs(dx) + jnp.abs(dy)) + jnp.abs(dz)
    return (dx * dx + dy * dy) + dz * dz


def pairwise_distance(a: jax.Array, b: jax.Array, metric: Metric = "l2") -> jax.Array:
    """Distance matrix between point sets.  a: (N, 3), b: (M, 3) -> (N, M).

    L2 returns *squared* distance (monotone equivalent, avoids sqrt);
    L1 returns the Manhattan distance (paper eq. 2).
    """
    diff = a[:, None, :] - b[None, :, :]
    return axis_distance(diff[..., 0], diff[..., 1], diff[..., 2], metric)


def point_distance(points: jax.Array, ref: jax.Array, metric: Metric = "l2") -> jax.Array:
    """Distance of every point to a single reference point.  (N, 3), (3,) -> (N,)."""
    diff = points - ref[None, :]
    return axis_distance(diff[..., 0], diff[..., 1], diff[..., 2], metric)


def fused_fps_step(
    points: jax.Array,
    dmin: jax.Array,
    last_idx: jax.Array,
    metric: Metric = "l2",
    valid: jax.Array | None = None,
):
    """One Ping-Pong-MAX step: distance + min-update + argmax in one fusion (C3).

    Returns (new_dmin, next_idx).  `valid` masks padded points out of the
    argmax (they keep dmin = -inf so they are never sampled).
    """
    ref = jnp.take(points, last_idx, axis=0)
    d = point_distance(points, ref, metric)
    new_dmin = jnp.minimum(dmin, d)
    score = new_dmin if valid is None else jnp.where(valid, new_dmin, -_BIG)
    next_idx = jnp.argmax(score)
    return new_dmin, next_idx


def fps(
    points: jax.Array,
    k: int,
    *,
    metric: Metric = "l2",
    start_idx: int | None = None,
    valid: jax.Array | None = None,
) -> jax.Array:
    """Sequential farthest point sampling.  points: (N, 3) -> indices (k,).

    The first sampled index defaults to the first VALID index — index 0 when
    no mask is given (PointNet++ convention), else argmax(valid), so a tile
    whose slot 0 is padding never seeds the sample with a fake point.  Pass
    `start_idx` to override.
    """
    n = points.shape[0]
    if k > n:
        raise ValueError(f"cannot sample {k} from {n} points")

    dmin0 = jnp.full((n,), _BIG, dtype=points.dtype)
    if start_idx is not None:
        idx0 = jnp.asarray(start_idx, dtype=jnp.int32)
    elif valid is not None:
        idx0 = jnp.argmax(valid).astype(jnp.int32)  # first valid slot
    else:
        idx0 = jnp.int32(0)

    def body(carry, _):
        dmin, last = carry
        new_dmin, nxt = fused_fps_step(points, dmin, last, metric, valid)
        return (new_dmin, jnp.asarray(nxt, jnp.int32)), last

    (_, _), sampled = jax.lax.scan(body, (dmin0, idx0), None, length=k)
    return sampled


def fps_batched(
    points: jax.Array,
    k: int,
    *,
    metric: Metric = "l2",
    valid: jax.Array | None = None,
) -> jax.Array:
    """FPS vmapped over any number of leading batch/tile dims.

    points: (..., N, 3) -> (..., k) int32 indices local to each tile.
    """
    batch_shape = points.shape[:-2]
    flat = points.reshape((-1,) + points.shape[-2:])
    if valid is not None:
        vflat = valid.reshape((-1, valid.shape[-1]))
        out = jax.vmap(lambda p, v: fps(p, k, metric=metric, valid=v))(flat, vflat)
    else:
        out = jax.vmap(lambda p: fps(p, k, metric=metric))(flat)
    return out.reshape(batch_shape + (k,))


# ---------------------------------------------------------------------------
# Quantized-coordinate L1 FPS (the faithful APD-CIM datapath: int16 coords,
# 19-bit distances).  Used by the energy model and the Pallas kernel oracle.
# ---------------------------------------------------------------------------

def quantize_coords(points: jax.Array, bits: int = 16):
    """Quantize float coords to signed ints on a uniform grid (paper: 16-bit PTQ).

    Returns (q_points int32 in [-2^(b-1), 2^(b-1)-1], scale, offset) such that
    points ~= q * scale + offset.
    """
    lo = jnp.min(points, axis=tuple(range(points.ndim - 1)), keepdims=True)
    hi = jnp.max(points, axis=tuple(range(points.ndim - 1)), keepdims=True)
    span = jnp.maximum(hi - lo, 1e-12)
    levels = (1 << bits) - 1
    scale = span / levels
    half = 1 << (bits - 1)
    q = jnp.clip(jnp.round((points - lo) / scale) - half, -half, half - 1)
    return q.astype(jnp.int32), scale, lo + half * scale


def fps_l1_quantized(points_q: jax.Array, k: int, *, start_idx: int = 0) -> jax.Array:
    """Integer L1 FPS over pre-quantized coords — exact APD-CIM arithmetic.

    points_q: (N, 3) int32 (16-bit range).  Distances are exact 19-bit ints.
    """
    n = points_q.shape[0]
    big = jnp.int32(2**30)

    def body(carry, _):
        dmin, last = carry
        ref = jnp.take(points_q, last, axis=0)
        d = jnp.sum(jnp.abs(points_q - ref[None, :]), axis=-1)  # <= 3*(2^16-1): 19 bits
        new_dmin = jnp.minimum(dmin, d.astype(dmin.dtype))
        nxt = jnp.argmax(new_dmin).astype(jnp.int32)
        return (new_dmin, nxt), last

    (_, _), sampled = jax.lax.scan(
        body, (jnp.full((n,), big, jnp.int32), jnp.asarray(start_idx, jnp.int32)), None, length=k
    )
    return sampled


# ---------------------------------------------------------------------------
# Sampling-quality metrics (used for the Fig 12a analogue: how good is the
# L1-approximate sample compared to exact-L2 FPS?)
# ---------------------------------------------------------------------------

def coverage_radius(points: jax.Array, sample_idx: jax.Array) -> jax.Array:
    """max_p min_s ||p - s||2 — the covering radius of the sampled subset (lower=better)."""
    centroids = jnp.take(points, sample_idx, axis=0)
    d = pairwise_distance(points, centroids, "l2")
    return jnp.sqrt(jnp.max(jnp.min(d, axis=1)))


def min_pairwise_separation(points: jax.Array, sample_idx: jax.Array) -> jax.Array:
    """min_{i!=j} ||s_i - s_j||2 — FPS maximises spread (higher=better)."""
    c = jnp.take(points, sample_idx, axis=0)
    d = pairwise_distance(c, c, "l2")
    k = c.shape[0]
    d = d + jnp.eye(k, dtype=d.dtype) * _BIG
    return jnp.sqrt(jnp.min(d))


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def fps_jit(points: jax.Array, k: int, metric: Metric = "l2") -> jax.Array:
    return fps(points, k, metric=metric)
