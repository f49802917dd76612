"""Pallas kernel: fused 3-nearest-neighbour search (FP-layer up-sampling).

For each query point, the 3 smallest distances + indices among P reference
points, computed as 3 successive (min, first-argmin, mask) extractions over
a VMEM-resident distance row — the same never-leave-VMEM dataflow as the
FPS kernel (the paper's kNN runs on the same APD-CIM + sorter).

Layout: queries block (bq, 3) on sublanes? No — distances are (bq, P):
queries on sublanes (bq multiple of 8), reference points on lanes (P
multiple of 128).  VMEM per program: bq*P*4 (dist) + 2 small outputs; for
bq=256, P=2048 that is 2 MB — double-bufferable on v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.fps import axis_distance
from repro.kernels import registry

_INF = 3.0e38  # python float: jnp scalars would be captured consts in the kernel


def _knn3_kernel(q_ref, p_ref, idx_ref, dist_ref, *, metric: str, k: int):
    """q_ref (bq, 3), p_ref (3, P) -> idx_ref (bq, k) int32, dist_ref (bq, k) f32."""
    diffs = [q_ref[:, i : i + 1] - p_ref[i : i + 1, :] for i in range(3)]
    d = axis_distance(*diffs, metric)  # (bq, P)
    bq, pp = d.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (bq, pp), 1)
    for t in range(k):
        m = jnp.min(d, axis=1, keepdims=True)  # (bq, 1)
        j = jnp.min(jnp.where(d == m, lane, pp), axis=1)  # first argmin
        idx_ref[:, t] = j.astype(jnp.int32)
        dist_ref[:, t] = m[:, 0]
        d = jnp.where(lane == j[:, None], _INF, d)  # mask out the extracted one


@functools.partial(jax.jit, static_argnames=("k", "metric", "bq", "interpret"))
def knn3_pallas(
    queries: jax.Array,
    points: jax.Array,
    *,
    k: int = 3,
    metric: str = "l2",
    bq: int = 256,
    interpret: bool = False,
):
    """queries: (Q, 3), points: (3, P) -> (idx (Q,k) int32, dist (Q,k) f32).

    Q needs no alignment: the query block is clamped to Q, sublane-aligned
    (multiple of 8 — queries live on sublanes), and the queries are padded
    internally up to a whole number of blocks with first-row copies, the
    same way fps_tiles pads lanes.  Padded rows compute real neighbours of
    the duplicated query and are sliced off before returning.
    """
    qn, three = queries.shape
    assert three == 3 and points.shape[0] == 3
    if qn < 1:
        raise ValueError(f"need at least one query, got Q={qn}")
    p = points.shape[1]
    if p % 128 != 0:
        raise ValueError(f"P={p} must be a multiple of 128")
    # clamp then sublane-align: bq > qn after clamping is fine (the whole
    # query set is one block), the padding below makes Q divide
    bq = min(bq, qn)
    bq += (-bq) % registry.SUBLANE
    queries, _ = registry.pad_to_multiple(queries, axis=0, multiple=bq)
    total = queries.shape[0]

    kernel = functools.partial(_knn3_kernel, metric=metric, k=k)
    idx, dist = pl.pallas_call(
        kernel,
        grid=(total // bq,),
        in_specs=[
            pl.BlockSpec((bq, 3), lambda i: (i, 0)),
            pl.BlockSpec((3, p), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i: (i, 0)),
            pl.BlockSpec((bq, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((total, k), jnp.int32),
            jax.ShapeDtypeStruct((total, k), jnp.float32),
        ],
        interpret=interpret,
        name="pc2im_knn3",
    )(queries, points)
    return idx[:qn], dist[:qn]
