"""Pallas kernel: in-VMEM farthest point sampling (APD-CIM + Ping-Pong-MAX, C1+C3).

Hardware mapping (paper -> TPU v5e):

  APD-CIM array holds one 2048-point tile (12 KB @ 16b)   -> the (3, P) tile
      lives in a VMEM block for the whole kernel; HBM sees ONE read.
  Ping-Pong-MAX CAM holds temporary distances in-situ     -> dmin lives in a
      VMEM scratch (never written to HBM); the min-update and the max-search
      happen in-register/VMEM each iteration (VPU tree reduction plays the
      role of the bit-serial CAM search).
  16 distances/cycle via PTG row activation               -> the VPU computes
      all P lane-parallel distances per iteration; the K-step loop is a
      lax.fori_loop INSIDE the kernel, so nothing round-trips to HBM.

Layout choices (TPU-native, all accepted by Mosaic):
  * points as (3, P) with P a multiple of 128 — coordinates on the sublane
    axis, points on the lane axis, so |x - x_ref| is a full-width VPU op.
  * dmin scratch as (1, P) f32.
  * every loop value stays a vector: each coordinate row of the reference
    point is read with a one-hot lane mask + lane sum (exact: one nonzero
    term), argmax is iota+select, and the sampled indices collect in a
    (1, k) vector that is stored once — Mosaic has no lane-axis
    dynamic_slice and no scalar store to VMEM.
  * output (T, 1, k) with block (None, 1, k): the block's last two dims
    equal the array's, which satisfies the (8, 128) tiling rule for any k.

Grid: one program per tile -> batched FPS over (T, 3, P) with zero padding
(equal-size MSP tiles map 1:1 onto grid steps — the C2 utilisation story).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fps import axis_distance

_BIG = 1e30


def _fps_kernel(points_ref, out_idx_ref, dmin_ref, *, k: int, metric: str):
    """One tile: points_ref (3, P) f32 -> out_idx_ref (1, k) int32."""
    p = points_ref.shape[-1]
    rows = [points_ref[i : i + 1, :] for i in range(3)]  # x, y, z: (1, P) each
    dmin_ref[...] = jnp.full((1, p), _BIG, jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, p), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

    def body(t, carry):
        last, out = carry  # last: int32 scalar, index of the newest sample
        hit = lane == last
        diffs = [r - jnp.sum(jnp.where(hit, r, 0.0), axis=1, keepdims=True) for r in rows]
        new_dmin = jnp.minimum(dmin_ref[...], axis_distance(*diffs, metric))
        dmin_ref[...] = new_dmin
        # in-situ max search (the CAM role): max + first-index-of-max
        m = jnp.max(new_dmin, axis=1, keepdims=True)
        nxt = jnp.min(jnp.where(new_dmin == m, lane, p)).astype(jnp.int32)
        return nxt, jnp.where(slot == t - 1, last, out)

    # `last` is carried as a scalar: a (1, 1) vector carry needs a relayout
    # Mosaic does not implement ("Sublane broadcast")
    init = (jnp.int32(0), jnp.zeros((1, k), jnp.int32))
    last, out = jax.lax.fori_loop(1, k, body, init)
    # the loop recorded samples 0..k-2; the final one goes in slot k-1
    out_idx_ref[...] = jnp.where(slot == k - 1, last, out)


@functools.partial(jax.jit, static_argnames=("k", "metric", "interpret"))
def fps_tiles_pallas(
    points: jax.Array, k: int, *, metric: str = "l1", interpret: bool = False
) -> jax.Array:
    """Batched tile FPS.  points: (T, 3, P) f32 -> (T, k) int32 local indices.

    P must be a multiple of 128 (lane width).  VMEM footprint per program:
    3*P*4 (tile) + P*4 (dmin) + k*4 — for P=2048 that is ~33 KB, far under
    the v5e 16MB VMEM: plenty of room for double-buffered grid pipelining.
    """
    t, three, p = points.shape
    assert three == 3, "points must be (T, 3, P)"
    if p % 128 != 0:
        raise ValueError(f"P={p} must be a multiple of 128 (TPU lane width)")

    kernel = functools.partial(_fps_kernel, k=k, metric=metric)
    out = pl.pallas_call(
        kernel,
        grid=(t,),
        in_specs=[pl.BlockSpec((None, 3, p), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((None, 1, k), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, 1, k), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, p), jnp.float32)],
        interpret=interpret,
        name="pc2im_fps_tile",
    )(points)
    return out[:, 0, :]
