"""Pallas kernel: split-concatenate W16A16 integer matmul (paper C4, SC-CIM).

The paper splits 16-bit weights into 4-bit *blocks* and 16-bit inputs into
4-bit *clusters*; cluster-block products become concatenations (shift-adds)
merged by a fused dense/sparse adder tree.  TPU mapping:

  4-bit planes in int8 containers  -> the MXU int8 path (4x bf16 byte-
                                      throughput, exact int32 accumulation)
  cluster-block product            -> one int8 x int8 -> int32 dot_general
  fused adder tree                 -> diagonal grouping: all plane pairs with
                                      i+j = d share one shift; sum the int32
                                      dots per diagonal FIRST, shift once
                                      (this is the dense/sparse tree fusion)
  periphery sign merge             -> top plane is the signed two's-complement
                                      remainder; handled by arithmetic shift

Why this matters on TPU: bf16 MXU matmuls have an 8-bit mantissa — a 16-bit
*integer* MAC cannot ride them exactly.  SC decomposition gives exact 16-bit
integer GEMM at 16 int8-dots ≈ 4 bf16-equivalent passes, mirroring the
paper's 4-cycle-per-input (vs 16 for bit-serial) trade.  W8A8 needs only
4 dots (= 1 pass) — paper's scheme generalises by plane count.

Grid: (M/bm, N/bn, K/bk), K innermost; per-diagonal int32 accumulators in
VMEM scratch; the f32 combine happens once on the last K step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import registry

PLANE_BITS = 4


def _split_planes_kernel(q: jax.Array, n_planes: int) -> list[jax.Array]:
    """Nibble-split int32 values (16-bit range): low planes in [0,15], top signed."""
    planes = []
    for i in range(n_planes - 1):
        planes.append((q >> (PLANE_BITS * i)) & 0xF)
    planes.append(q >> (PLANE_BITS * (n_planes - 1)))  # arithmetic: signed top
    return planes


def _sc_matmul_kernel(
    x_ref, w_ref, out_ref, *accs, n_planes_x: int, n_planes_w: int, k_steps: int
):
    """One (bm, bn) tile; K-accumulation across grid axis 2.

    accs: one int32 VMEM scratch (bm, bn) per diagonal d in [0, nx+nw-2].
    """
    kidx = pl.program_id(2)
    n_diags = n_planes_x + n_planes_w - 1

    @pl.when(kidx == 0)
    def _init():
        for d in range(n_diags):
            accs[d][...] = jnp.zeros_like(accs[d])

    xp = _split_planes_kernel(x_ref[...], n_planes_x)  # each (bm, bk) int32
    wp = _split_planes_kernel(w_ref[...], n_planes_w)  # each (bk, bn) int32
    for i in range(n_planes_x):
        for j in range(n_planes_w):
            # int8-range operands -> MXU int path, exact int32 accumulation
            dot = jax.lax.dot_general(
                xp[i].astype(jnp.int8),
                wp[j].astype(jnp.int8),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            accs[i + j][...] += dot

    @pl.when(kidx == k_steps - 1)
    def _combine():
        # periphery merge: one shift per diagonal (the fused adder tree)
        out = jnp.zeros(out_ref.shape, jnp.float32)
        for d in range(n_diags):
            out = out + accs[d][...].astype(jnp.float32) * float(1 << (PLANE_BITS * d))
        out_ref[...] = out


@functools.partial(
    jax.jit,
    static_argnames=("n_planes_x", "n_planes_w", "bm", "bn", "bk", "interpret"),
)
def sc_matmul_pallas(
    x_q: jax.Array,
    w_q: jax.Array,
    *,
    n_planes_x: int = 4,
    n_planes_w: int = 4,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """x_q: (M, K) int32 (16-bit range), w_q: (K, N) int32 -> (M, N) f32.

    Result is the exact integer product whenever each diagonal partial sum
    stays within f32's 24-bit exact-integer window after the shift; the
    int32 per-diagonal accumulation itself is always exact (|plane| <= 15,
    so |diag dot| <= 4 * 225 * K -> exact for K up to ~2.3M).

    VMEM per program: bm*bk + bk*bn int32 operands + 7 * bm*bn int32 accs.
    Defaults (128,128,512): 64KB + 256KB + 448KB ~ 0.77MB — fits v5e VMEM
    with double buffering.
    """
    m, k = x_q.shape
    k2, n = w_q.shape
    assert k == k2
    # M <= bm is one whole block; a longer M is padded with copies of row 0
    # up to a whole number of blocks, and the pad rows are sliced off
    bm = min(bm, m)
    x_q, _ = registry.pad_to_multiple(x_q, axis=0, multiple=bm)
    if n % bn or k % bk:
        bn, bk = min(bn, n), min(bk, k)
        if n % bn or k % bk:
            raise ValueError(f"shapes ({m},{k},{n}) not tileable by ({bm},{bn},{bk})")
    k_steps = k // bk
    n_diags = n_planes_x + n_planes_w - 1

    kernel = functools.partial(
        _sc_matmul_kernel,
        n_planes_x=n_planes_x,
        n_planes_w=n_planes_w,
        k_steps=k_steps,
    )
    return pl.pallas_call(
        kernel,
        grid=(x_q.shape[0] // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((x_q.shape[0], n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32) for _ in range(n_diags)],
        interpret=interpret,
        name="pc2im_sc_matmul",
    )(x_q, w_q)[:m]
