"""Plain float32 reference of the served PointNet2 (PC2IM preprocessing).

Written from the configuration's description in `jax.numpy`, with no import
from the program: one cloud at a time (callers `vmap` over blocks of clouds),
no kernels, no cache, no batching across clouds. What it computes:

* serving fit: a cloud smaller than `n_points` repeats its last point; a
  larger one keeps the rows `round(linspace(0, n - 1, n_points))`; seg
  logits map back to every input row (padding rows dropped, dropped rows
  take their nearest kept row, ties to the earlier one).
* per set-abstraction (SA) stage: median-split partition into 2^d tiles of
  equal size (split axis = widest extent, stable sort), farthest point
  sampling in each tile under the L1 metric starting at the tile's first
  point, and the lattice query (the first `nsample` tile points with L1
  distance <= 1.6 x radius, empty slots repeating the first hit).
  d is the largest depth <= `msp_depth` that keeps tiles at least four
  times their sample count with both counts divisible by 2^d.
* input: each point is xyz and `in_features` (default 0) channels more;
  sampling and queries read xyz alone, the first SA MLP and the finest
  feature-propagation skip read both.
* features with delayed aggregation: the per-point MLP on [xyz, features],
  then a gather over each neighbourhood and a masked max.
* cls: the global MLP on [xyz, features] of the last level, a max over
  points, the head. seg: feature propagation by 3 nearest neighbours
  (squared L2, ties to the lower index) with inverse-distance weights.
* each MLP layer: linear -> LayerNorm (eps 1e-5) -> ReLU; the head has no
  LayerNorm and no ReLU after its last layer.
* a quantized linear ("sc_w16a16" / "sc_w8a8"): symmetric per-tensor
  quantization of the activation and of the weight to `bits`, the integer
  product, the product of the two scales. Here the activation's scale
  spans one cloud's rows; the program's spans its micro-batch, so the two
  differ by rounding of the activation codes.

Distances are summed in the order (x + y) + z, so that ties between
distances break the same way as in any implementation that keeps IEEE
float32 arithmetic in that order.

Matrix products take an explicit number of bf16 passes: 6 is
`jax.default_matmul_precision("highest")`, 3 is the "high" control, which
splits each operand into a bf16 high and low part and drops the low x low
product on every backend alike.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LATTICE_RANGE_FACTOR = 1.6
LN_EPS = 1e-5
FPS_INIT = 1e30
QUANT_BITS = {"none": None, "sc_w16a16": 16, "sc_w8a8": 8}


# -- weights ------------------------------------------------------------------


def _linear_init(key, d_in: int, d_out: int):
    wkey, _ = jax.random.split(key)
    w = jax.random.normal(wkey, (d_in, d_out)) * (1.0 / jnp.sqrt(d_in))
    return {"w": w.astype(jnp.float32), "b": jnp.zeros((d_out,), jnp.float32)}


def _mlp_init(key, channels, *, norm: bool = True):
    keys = jax.random.split(key, len(channels) - 1)
    layers = []
    for i, (cin, cout) in enumerate(zip(channels[:-1], channels[1:])):
        lay = {"lin": _linear_init(keys[i], cin, cout)}
        if norm:
            lay["ln"] = {
                "g": jnp.ones((cout,), jnp.float32),
                "b": jnp.zeros((cout,), jnp.float32),
            }
        layers.append(lay)
    return {"layers": layers}


def init_params(key, model: dict):
    """Seeded weights for `model` (the config file's "model" group).

    The tree is the program's parameter layout: {"sa": [mlp...],
    "global"|"fp": ..., "head": mlp}, each mlp {"layers": [{"lin": {"w",
    "b"}, "ln": {"g", "b"}}]}; weights N(0, 1/fan_in), biases 0, LayerNorm
    gain 1 and shift 0.
    """
    keys = iter(jax.random.split(key, 64))
    params = {"sa": []}
    point_c = 3 + model.get("in_features", 0)
    c_in = point_c
    for sa in model["sa"]:
        params["sa"].append(_mlp_init(next(keys), [c_in] + list(sa["mlp"])))
        c_in = sa["mlp"][-1] + 3
    sa_out = model["sa"][-1]["mlp"][-1]
    if model["task"] == "cls":
        params["global"] = _mlp_init(next(keys), [sa_out + 3] + list(model["global_mlp"]))
        head = [model["global_mlp"][-1]] + list(model["head"]) + [model["n_classes"]]
        params["head"] = _mlp_init(next(keys), head, norm=False)
        return params
    params["fp"] = []
    skips = [point_c] + [sa["mlp"][-1] for sa in model["sa"][:-1]]
    c_coarse = sa_out
    fp = model["fp_mlp"]
    for i, skip_c in enumerate(reversed(skips)):
        cout = fp[min(i, len(fp) - 1)]
        params["fp"].append(_mlp_init(next(keys), [c_coarse + skip_c, cout, cout]))
        c_coarse = cout
    head = [c_coarse] + list(model["head"]) + [model["n_classes"]]
    params["head"] = _mlp_init(next(keys), head, norm=False)
    return params


# -- serving fit (host side) ---------------------------------------------------


def fit_rows(n: int, n_points: int) -> np.ndarray:
    """Row of the input cloud that fills each of the `n_points` served slots."""
    if n > n_points:
        return np.linspace(0, n - 1, n_points).round().astype(np.int64)
    return np.minimum(np.arange(n_points), n - 1)


def output_rows(n: int, n_points: int) -> np.ndarray:
    """Served slot whose seg logits score each of the `n` input rows."""
    if n <= n_points:
        return np.arange(n)
    kept = fit_rows(n, n_points)
    rows = np.arange(n)
    right = np.clip(np.searchsorted(kept, rows, side="left"), 0, n_points - 1)
    left = np.clip(right - 1, 0, n_points - 1)
    return np.where(rows - kept[left] <= kept[right] - rows, left, right)


# -- preprocessing ---------------------------------------------------------------


def msp_depth(n: int, m: int, depth: int) -> int:
    """Partition depth of a stage sampling `m` of `n` points (see module doc)."""
    while depth > 0 and (n >> depth) < 4 * max(1, m >> depth):
        depth -= 1
    while depth > 0 and (n % (1 << depth) or m % (1 << depth)):
        depth -= 1
    return depth


def _l1(c, p):
    """L1 distances from each row of c (..., 3) to each row of p (P, 3)."""
    dx = jnp.abs(c[..., 0:1] - p[:, 0])
    dy = jnp.abs(c[..., 1:2] - p[:, 1])
    dz = jnp.abs(c[..., 2:3] - p[:, 2])
    return (dx + dy) + dz


def partition(xyz, depth: int):
    """Median split of one cloud (N, 3) into (2^depth, N / 2^depth) indices."""
    tiles = jnp.arange(xyz.shape[0], dtype=jnp.int32)[None, :]
    for _ in range(depth):
        t, p = tiles.shape
        coords = xyz[tiles]  # (t, p, 3)
        extent = coords.max(axis=1) - coords.min(axis=1)
        axis = jnp.argmax(extent, axis=-1)
        key = jnp.take_along_axis(coords, axis[:, None, None], axis=2)[..., 0]
        order = jnp.argsort(key, axis=1, stable=True)
        tiles = jnp.take_along_axis(tiles, order, axis=1).reshape(2 * t, p // 2)
    return tiles


def fps(pts, k: int):
    """L1 farthest point sampling of one tile (P, 3): k local indices."""
    def step(carry, _):
        dmin, last = carry
        d = _l1(pts[last][None, :], pts)[0]
        dmin = jnp.minimum(dmin, d)
        return (dmin, jnp.argmax(dmin).astype(jnp.int32)), last

    dmin0 = jnp.full((pts.shape[0],), FPS_INIT, jnp.float32)
    _, idx = jax.lax.scan(step, (dmin0, jnp.int32(0)), None, length=k)
    return idx


def lattice_query(pts, centroids, radius: float, nsample: int):
    """First `nsample` points of a tile within L1 range: (idx, mask)."""
    d = _l1(centroids, pts)  # (K, P)
    hit = d <= float(radius * LATTICE_RANGE_FACTOR)
    order = jnp.argsort(jnp.logical_not(hit).astype(jnp.int8), axis=1, stable=True)
    first = order[:, :nsample].astype(jnp.int32)
    # a tile smaller than nsample leaves the slots past its points empty
    first = jnp.pad(first, ((0, 0), (0, nsample - first.shape[1])))
    mask = jnp.arange(nsample)[None, :] < hit.sum(axis=1)[:, None]
    idx = jnp.where(mask, first, first[:, :1])
    return jnp.where(mask[:, :1], idx, 0), mask


def sa_preprocess(xyz, sa: dict, depth_cap: int):
    """One SA stage's (centroid index, neighbour index, mask) for one cloud."""
    n = xyz.shape[0]
    m = sa["n_centroids"]
    depth = msp_depth(n, m, depth_cap)
    tiles = partition(xyz, depth)  # (T, P)
    k = m >> depth
    coords = xyz[tiles]  # (T, P, 3)
    local_c = jax.vmap(lambda p: fps(p, k))(coords)  # (T, k)
    cxyz = jnp.take_along_axis(coords, local_c[..., None], axis=1)
    lidx, mask = jax.vmap(
        lambda p, c: lattice_query(p, c, sa["radius"], sa["nsample"])
    )(coords, cxyz)
    cidx = jnp.take_along_axis(tiles, local_c, axis=1).reshape(m)
    nidx = jax.vmap(lambda t, i: t[i])(tiles, lidx).reshape(m, sa["nsample"])
    return cidx, nidx, mask.reshape(m, sa["nsample"])


# -- features --------------------------------------------------------------------


def _bf16(x):
    # an explicit rounding: a float32 -> bfloat16 -> float32 round trip may be
    # elided by the compiler (excess precision), reduce_precision may not
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split_bf16(x):
    hi = _bf16(x)
    return hi.astype(jnp.bfloat16), _bf16(x - hi).astype(jnp.bfloat16)


def matmul(a, b, passes: int):
    """a @ b in float32 at 6 bf16 passes ("highest") or 3 ("high")."""
    if passes == 6:
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if passes != 3:
        raise ValueError(f"passes must be 6 or 3, got {passes}")
    a_hi, a_lo = _split_bf16(a)
    b_hi, b_lo = _split_bf16(b)
    dot = functools.partial(jnp.matmul, preferred_element_type=jnp.float32)
    return dot(a_hi, b_hi) + (dot(a_hi, b_lo) + dot(a_lo, b_hi))


def _quantize(x, bits: int):
    qmax = (1 << (bits - 1)) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax - 1, qmax), scale


def linear(p, x, quant: str, passes: int):
    """Dense layer over the rows of x, float or symmetric-quantized."""
    bits = QUANT_BITS[quant]
    if bits is None:
        y = matmul(x, p["w"], passes)
    else:
        lead = x.shape[:-1]
        qx, sx = _quantize(x.reshape(-1, x.shape[-1]), bits)
        qw, sw = _quantize(p["w"], bits)
        y = (matmul(qx, qw, passes) * (sx * sw)).reshape(lead + (p["w"].shape[1],))
    return y + p["b"]


def mlp(p, x, quant: str, passes: int, *, final_act: bool = True):
    """[linear -> LayerNorm -> ReLU] per layer (head: no LayerNorm)."""
    n = len(p["layers"])
    for i, lay in enumerate(p["layers"]):
        x = linear(lay["lin"], x, quant, passes)
        if "ln" in lay:
            mu = x.mean(axis=-1, keepdims=True)
            var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
            x = (x - mu) / jnp.sqrt(var + LN_EPS) * lay["ln"]["g"] + lay["ln"]["b"]
        if final_act or i < n - 1:
            x = jnp.maximum(x, 0.0)
    return x


def _knn3(q, r):
    """3 nearest rows of r (M, 3) for each row of q (N, 3): (idx, sq dist)."""
    dx, dy, dz = (q[:, i:i + 1] - r[:, i] for i in range(3))
    d = (dx * dx + dy * dy) + dz * dz
    idxs, dists = [], []
    rows = jnp.arange(q.shape[0])
    for _ in range(3):
        j = jnp.argmin(d, axis=1)
        idxs.append(j)
        dists.append(d[rows, j])
        d = d.at[rows, j].set(jnp.inf)
    return jnp.stack(idxs, 1), jnp.stack(dists, 1)


def forward(params, model: dict, cloud, *, quant: str, passes: int = 6):
    """Logits of one fitted cloud (n_points, 3 + in_features): cls (C,), seg (N, C)."""
    xyz = cloud[:, :3]
    levels = [(xyz, cloud[:, 3:] if model.get("in_features", 0) else None)]
    for sa, p in zip(model["sa"], params["sa"]):
        pts, feats = levels[-1]
        cidx, nidx, mask = sa_preprocess(pts, sa, model["msp_depth"])
        x = pts if feats is None else jnp.concatenate([pts, feats], -1)
        pointwise = mlp(p, x, quant, passes)
        grouped = jnp.where(mask[..., None], pointwise[nidx], -jnp.inf)
        pooled = jnp.where(mask.any(-1, keepdims=True), grouped.max(axis=1), 0.0)
        levels.append((pts[cidx], pooled))
    if model["task"] == "cls":
        pts, feats = levels[-1]
        x = mlp(params["global"], jnp.concatenate([pts, feats], -1), quant, passes)
        return mlp(params["head"], x.max(axis=0), quant, passes, final_act=False)
    coarse_xyz, coarse_f = levels[-1]
    n_fp = len(params["fp"])
    for i, p in enumerate(params["fp"]):
        fine_xyz, fine_f = levels[n_fp - 1 - i]
        idx, dist = _knn3(fine_xyz, coarse_xyz)
        w = 1.0 / (dist + 1e-8)
        w = w / w.sum(-1, keepdims=True)
        interp = (coarse_f[idx] * w[..., None]).sum(axis=1)
        if i < n_fp - 1:
            skip = fine_f
        else:  # the raw points: xyz and the input features
            skip = fine_xyz if fine_f is None else jnp.concatenate([fine_xyz, fine_f], -1)
        coarse_f = mlp(p, jnp.concatenate([interp, skip], -1), quant, passes)
        coarse_xyz = fine_xyz
    return mlp(params["head"], coarse_f, quant, passes, final_act=False)


def make_block_fn(model: dict, *, quant: str, passes: int = 6):
    """jit-compiled reference over a block of fitted clouds (B, N, 3 + in_features)."""
    def block(params, clouds):
        return jax.vmap(lambda c: forward(params, model, c, quant=quant, passes=passes))(clouds)

    return jax.jit(block)
