"""Batched PreprocessEngine — end-to-end (B, N, 3) preprocessing in one launch.

The per-cloud pipelines in core/preprocess.py are the semantic oracles; this
module is how production traffic runs them.  A `PreprocessEngine` is built
once from an `EngineConfig` (pipeline name, partition depth, metric, query
type, backend) and maps a whole batch of clouds to a batched
`PreprocessResult`:

    engine = PreprocessEngine(EngineConfig(pipeline="pc2im", n_centroids=128,
                                           radius=0.3, nsample=16, depth=3))
    res = engine(points)          # points (B, N, 3) -> fields lead with B

The key dataflow move (the reason this is faster than `vmap` over the
per-cloud functions): batch and MSP tiles are FOLDED INTO ONE TILE AXIS.
After partitioning, the B clouds' 2^depth tiles each become a (B·T, P, 3)
tensor, and the Pallas FPS / lattice kernels see a single grid of B·T
programs instead of B separate launches — exactly the paper's C2 story
(equal-size tiles -> a perfectly uniform grid) extended to the batch dim.

Backend handling goes through kernels/registry: "auto" resolves to the
Pallas kernels on TPU (interpret mode elsewhere) and the XLA reference path
otherwise.  Ops with no kernel counterpart (masked ball query, the ragged
grid partition of baseline2) always take the XLA path — the registry's
documented fallback — so every pipeline works on every backend and is
bitwise identical to its per-cloud oracle.
"""

from __future__ import annotations

import dataclasses
import functools
import io
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import partition as part_mod
from repro.core import preprocess as pp_mod
from repro.core import query as query_mod
from repro.core.preprocess import PreprocessResult
from repro.core.query import NeighborSet
from repro.kernels.fps.ops import fps_tiles
from repro.kernels.lattice.ops import lattice_query_tiles

Pipeline = Literal["baseline1", "baseline2", "pc2im"]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static description of one preprocessing pipeline instance.

    metric/query default to the pipeline's canonical choice (pc2im: L1 +
    lattice; baselines: L2 + ball) and can be overridden to mix, e.g. MSP
    tiles with an L2 ball query for ablations.
    """

    pipeline: Pipeline = "pc2im"
    n_centroids: int = 128
    radius: float = 0.3
    nsample: int = 16
    depth: int = 3  # MSP: tiles = 2^depth (pc2im only)
    axis_mode: str = "widest"
    metric: str | None = None  # None -> pipeline default
    query: str | None = None  # None -> pipeline default
    grid: int = 2  # baseline2 spatial grid
    capacity: int | None = None  # baseline2 tile capacity (None -> 2x mean)
    backend: str = "auto"  # "auto" | "pallas" | "xla"  (kernels/registry)
    interpret: bool | None = None  # None -> interpret off-TPU

    @property
    def resolved_metric(self) -> str:
        """FPS distance metric with the None placeholder resolved."""
        if self.metric is not None:
            return self.metric
        return "l1" if self.pipeline == "pc2im" else "l2"

    @property
    def resolved_query(self) -> str:
        """Neighbour-query kind with the None placeholder resolved."""
        if self.query is not None:
            return self.query
        return "lattice" if self.pipeline == "pc2im" else "ball"

    @property
    def n_tiles(self) -> int:
        """Tiles per cloud seen by the kernels (1 for the global baseline1)."""
        if self.pipeline == "pc2im":
            return 1 << self.depth
        if self.pipeline == "baseline2":
            return self.grid**3
        return 1


def clamp_depth(n_points: int, n_centroids: int, depth: int) -> int:
    """Largest usable MSP depth <= `depth` for a given cloud/sample size.

    Keeps tiles no smaller than 4x the per-tile sample count and requires
    both N and n_centroids to split evenly (the MSP equal-tile property).
    Shared by models/ and serve/ so every consumer agrees on the shape.
    """
    while depth > 0 and (n_points >> depth) < 4 * max(1, n_centroids >> depth):
        depth -= 1
    while depth > 0 and (n_points % (1 << depth) or n_centroids % (1 << depth)):
        depth -= 1
    return depth


class PreprocessEngine:
    """jit-compiled batched preprocessing: (B, N, 3) -> PreprocessResult.

    Output fields lead with the batch dim: centroid_idx (B, M),
    centroid_xyz (B, M, 3), neighbors.idx/mask (B, M, nsample),
    centroid_valid (B, M), with M = n_centroids and indices global per cloud.
    A single (N, 3) cloud is accepted and returns unbatched fields.
    """

    def __init__(self, config: EngineConfig):
        if config.pipeline not in ("baseline1", "baseline2", "pc2im"):
            raise ValueError(f"unknown pipeline {config.pipeline!r}")
        if config.pipeline == "pc2im" and config.n_centroids % config.n_tiles:
            raise ValueError(
                f"n_centroids={config.n_centroids} not divisible by "
                f"2^depth={config.n_tiles} tiles"
            )
        self.config = config
        self._raw_fn = {
            "baseline1": self._baseline1,
            "baseline2": self._baseline2,
            "pc2im": self._pc2im,
        }[config.pipeline]
        self._fn = jax.jit(self._raw_fn)

    def __call__(self, points: jax.Array) -> PreprocessResult:
        """Run the jit-compiled pipeline on (B, N, 3) or single (N, 3) coords.

        See the class docstring for the output layout.
        """
        return self._dispatch(points, self._fn)

    def raw(self, points: jax.Array) -> PreprocessResult:
        """Un-jitted equivalent of calling the engine, for composition.

        Same validation and shape handling as `__call__`.
        `PC2IMAccelerator` builds its preprocess-stage sub-artifact by
        chaining the per-SA-stage engines inside ONE enclosing jit; tracing
        the raw pipeline keeps that artifact a single jaxpr instead of a
        nest of engine dispatches.  Outside a trace, prefer `__call__`.
        """
        return self._dispatch(points, self._raw_fn)

    def _dispatch(self, points: jax.Array, fn) -> PreprocessResult:
        if points.ndim == 2:
            if points.shape[-1] != 3:
                raise ValueError(f"expected (B, N, 3) or (N, 3), got {points.shape}")
            res = fn(points[None])
            return jax.tree.map(lambda x: x[0], res)
        if points.ndim != 3 or points.shape[-1] != 3:
            raise ValueError(f"expected (B, N, 3) or (N, 3), got {points.shape}")
        cfg = self.config
        if cfg.pipeline == "pc2im" and points.shape[1] % cfg.n_tiles:
            raise ValueError(
                f"N={points.shape[1]} not divisible by 2^depth={cfg.n_tiles}; "
                f"pad the clouds or lower depth (see clamp_depth)"
            )
        return fn(points)

    # -- pipelines -----------------------------------------------------------

    def _baseline1(self, points: jax.Array) -> PreprocessResult:
        """Global FPS + global ball query; the B clouds ARE the kernel tiles."""
        cfg = self.config
        b = points.shape[0]
        with jax.named_scope("fps"):
            cidx = fps_tiles(
                points, cfg.n_centroids, metric=cfg.resolved_metric,
                backend=cfg.backend, interpret=cfg.interpret,
            )  # (B, M)
            cxyz = jnp.take_along_axis(points, cidx[..., None], axis=1)  # (B, M, 3)
        with jax.named_scope("query"):
            nbrs = jax.vmap(
                lambda p, c: query_mod.ball_query(p, c, cfg.radius, cfg.nsample)
            )(points, cxyz)
        return PreprocessResult(
            cidx, cxyz, nbrs, jnp.ones((b, cfg.n_centroids), bool)
        )

    def _baseline2(self, points: jax.Array) -> PreprocessResult:
        """TiPU-like ragged grid tiles: masked flow, always the XLA path.

        No kernel has valid-mask support — the registry's documented
        fallback.
        """
        cfg = self.config
        return jax.vmap(
            lambda p: pp_mod.preprocess_baseline2(
                p, cfg.n_centroids, cfg.radius, cfg.nsample,
                grid=cfg.grid, capacity=cfg.capacity,
            )
        )(points)

    def _pc2im(self, points: jax.Array) -> PreprocessResult:
        """MSP tiles + local FPS + local query.

        Batch x tiles fold into one (B·T, P) kernel grid axis.
        """
        cfg = self.config
        b, n, _ = points.shape
        t = cfg.n_tiles
        p = n // t
        k = cfg.n_centroids // t

        with jax.named_scope("partition"):
            # per-cloud MSP (batched argsorts); tiles (B, T, P) global-per-cloud
            tiles = jax.vmap(
                lambda pts: part_mod.median_partition(
                    pts, cfg.depth, axis_mode=cfg.axis_mode
                ).tiles
            )(points)

            # FOLD: (B, T, P, 3) -> (B·T, P, 3); one kernel grid for all clouds
            coords = jnp.take_along_axis(points[:, None], tiles[..., None], axis=2)
            flat_tiles = tiles.reshape(b * t, p)
            flat_coords = coords.reshape(b * t, p, 3)

        with jax.named_scope("fps"):
            local_c = fps_tiles(
                flat_coords, k, metric=cfg.resolved_metric,
                backend=cfg.backend, interpret=cfg.interpret,
            )  # (B·T, k) local
            cidx = jnp.take_along_axis(flat_tiles, local_c, axis=1)  # global
            cxyz = jnp.take_along_axis(flat_coords, local_c[..., None], axis=1)

        with jax.named_scope("query"):
            if cfg.resolved_query == "lattice":
                nbrs_local = lattice_query_tiles(
                    flat_coords, cxyz, cfg.radius, cfg.nsample,
                    backend=cfg.backend, interpret=cfg.interpret,
                )
            else:  # per-tile ball query: no kernel counterpart, XLA path
                nbrs_local = jax.vmap(
                    lambda c, cx: query_mod.ball_query(c, cx, cfg.radius, cfg.nsample)
                )(flat_coords, cxyz)

        with jax.named_scope("group"):
            # local tile slots -> global point indices
            nidx = jnp.take_along_axis(flat_tiles[:, None, :], nbrs_local.idx, axis=2)

        m = t * k
        return PreprocessResult(
            centroid_idx=cidx.reshape(b, m),
            centroid_xyz=cxyz.reshape(b, m, 3),
            neighbors=NeighborSet(
                idx=nidx.reshape(b, m, cfg.nsample),
                mask=nbrs_local.mask.reshape(b, m, cfg.nsample),
            ),
            centroid_valid=jnp.ones((b, m), bool),  # MSP: zero padding
        )


@functools.lru_cache(maxsize=None)
def get_engine(config: EngineConfig) -> PreprocessEngine:
    """Engine cache: one jitted engine per distinct config.

    models/ and serve/ build engines per SA stage; the cache makes that free.
    """
    return PreprocessEngine(config)


# -- result trees: size accounting, per-row access, serialization -------------
#
# A "result tree" is any pytree of arrays built from PreprocessResults — one
# batched result, or the tuple-per-SA-stage the accelerator's
# preprocess_stage emits.  The cross-request preprocess cache
# (serve/preprocess_cache.py) stores these per request row and re-assembles
# them per micro-batch, so the row/stack/byte helpers live HERE, next to the
# engine that defines the layout, and stay pure tree manipulation.


def result_nbytes(res) -> int:
    """Total bytes of every array leaf in a result tree.

    Works on host (numpy) and device (jax.Array) leaves alike — both expose
    `.nbytes` — so the cache's byte budget accounts exactly what it retains.
    """
    return int(sum(x.nbytes for x in jax.tree_util.tree_leaves(res)))


def result_to_host(res):
    """Materialize every leaf of a result tree as a WRITABLE host numpy array.

    Blocks on (and transfers) device leaves.  Writability matters: on the
    CPU backend `np.asarray(jax_array)` can be a read-only view of the
    device buffer, which would make the cache-hit splice
    (`result_set_row`) raise — so read-only leaves are copied.
    """

    def one(x):
        arr = np.asarray(x)
        return arr if arr.flags.writeable else arr.copy()

    return jax.tree.map(one, res)


def result_row(res, i: int):
    """Slice row `i` off every leaf's leading (batch) dim of a result tree.

    The per-request payload the preprocess cache stores: one cloud's
    centroids/neighborhoods out of a batched PreprocessResult.
    """
    return jax.tree.map(lambda x: x[i], res)


def result_stack(rows, total: int | None = None):
    """Stack per-row result trees back into one batched tree.

    `rows` are `result_row`-shaped trees (all the same structure);
    `total` > len(rows) appends zero filler rows so the stacked batch hits a
    static batch dim — filler rows mirror assemble_batch's zero batch rows,
    whose outputs the scatter step drops.
    """
    rows = list(rows)
    if not rows:
        raise ValueError("need at least one row to stack")
    if total is not None and total > len(rows):
        filler = jax.tree.map(np.zeros_like, rows[0])
        rows.extend([filler] * (total - len(rows)))
    return jax.tree.map(lambda *xs: np.stack(xs), *rows)


def result_set_row(res, i: int, row) -> None:
    """Write a per-row tree into row `i` of a batched HOST result tree.

    In-place: `res` leaves must be writable numpy arrays (use
    `result_to_host` first).  This is the cache-hit splice — a hit row's
    cached neighborhoods replace whatever the batched preprocess computed
    for that row before the feature stage consumes the tree.
    """
    dst_leaves, treedef = jax.tree_util.tree_flatten(res)
    src_leaves = treedef.flatten_up_to(row)
    for dst, src in zip(dst_leaves, src_leaves):
        dst[i] = src


def serialize_result(res) -> bytes:
    """Pack a result tree's leaves into one portable npz byte blob.

    Leaves are stored in tree-flatten order; the tree STRUCTURE is not
    encoded — pass a structurally identical template to
    `deserialize_result` to rebuild (every cache entry of one runtime
    shares a single structure, so shipping it per blob would be waste).
    """
    leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(res)]
    buf = io.BytesIO()
    np.savez(buf, *leaves)
    return buf.getvalue()


def deserialize_result(blob: bytes, like):
    """Rebuild a result tree from `serialize_result` bytes.

    `like` supplies the tree structure (any tree with the same topology,
    e.g. a live entry's payload); leaf arrays come from the blob, dtype and
    shape preserved bitwise.
    """
    with np.load(io.BytesIO(blob)) as data:
        leaves = [data[k] for k in data.files]
    treedef = jax.tree_util.tree_structure(like)
    return jax.tree_util.tree_unflatten(treedef, leaves)
