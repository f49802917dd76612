"""PointNet++ (PointNet2) — the paper's evaluation model — with PC2IM preprocessing.

Set-abstraction (SA) stages: sample centroids (FPS), query neighbours, learn
per-point features (MLP), max-pool per neighbourhood.  Feature-propagation
(FP) stages (segmentation): 3-NN inverse-distance interpolation + unit MLPs.

PC2IM switches, all config-selectable (benchmarked in fig12a/fig13):
  preproc    : "baseline1" (global L2 FPS + ball)  |  "baseline2" (grid tiles)
               | "pc2im" (MSP + L1 FPS + lattice query)
  aggregation: "standard" (group->mlp->pool) | "delayed" (mlp->group->pool, C5)
  quant      : "none" | "sc_w16a16" (C4; applies to every MLP linear via the
               ExecutionPolicy threaded through forward — see core/policy.py)

Every stage runs under a `jax.named_scope`, so each HLO op's `op_name`
metadata names its layer (the compiled program is otherwise unchanged):
`sa{i}/partition`, `sa{i}/fps`, `sa{i}/query` (the engine's stages, see
core/engine.py), `sa{i}/group` (slot -> index remap, feature gathers,
masked max-pool), `sa{i}/mlp`, `global/mlp`, `global/pool`, `head`, and
`fp{i}/knn`, `fp{i}/interp`, `fp{i}/mlp`; SA and FP stages count from 1,
FP from the coarsest.  The benchmark's trace readers map a compiled
program's instruction names to these paths (bench/benchlib/scopes.py).

Note on delayed aggregation: standard SA feeds the MLP relative coordinates
(neighbour - centroid), which cannot be precomputed per point.  Following
Mesorasi [8] (which the paper adopts), the delayed path feeds *absolute*
coords + features through the per-point MLP and aggregates afterwards.
"""

from __future__ import annotations

import dataclasses
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core import grouping as G
from repro.core import query as Q
from repro.core.engine import EngineConfig, clamp_depth, get_engine
from repro.core.policy import ExecutionPolicy, resolve_policy
from repro.kernels.knn3 import knn3
from repro.models import nn


@dataclasses.dataclass(frozen=True)
class SAConfig:
    n_centroids: int
    radius: float
    nsample: int
    mlp: tuple[int, ...]  # hidden/out channels (input inferred)


@dataclasses.dataclass(frozen=True)
class PointNet2Config:
    name: str = "pointnet2"
    task: Literal["cls", "seg"] = "cls"
    n_points: int = 1024
    n_classes: int = 8
    in_features: int = 0  # extra per-point features beyond xyz
    sa: tuple[SAConfig, ...] = (
        SAConfig(256, 0.2, 32, (64, 64, 128)),
        SAConfig(64, 0.4, 32, (128, 128, 256)),
    )
    global_mlp: tuple[int, ...] = (256, 512, 1024)  # final global SA (cls)
    fp_mlp: tuple[int, ...] = (256, 128)  # per-FP-stage out channels (seg)
    head: tuple[int, ...] = (512, 256)
    preproc: Literal["baseline1", "baseline2", "pc2im"] = "pc2im"
    aggregation: Literal["standard", "delayed"] = "delayed"
    quant: Literal["none", "sc_w16a16", "sc_w8a8"] = "none"
    msp_depth: int = 2  # MSP tiles = 2^depth (pc2im preproc)
    preproc_backend: str = "auto"  # kernel registry backend for preprocessing

    @property
    def family(self) -> str:
        return "pointcloud"


def init_params(key, cfg: PointNet2Config):
    keys = iter(jax.random.split(key, 64))
    params: dict = {"sa": []}
    c_in = 3 + cfg.in_features
    for sa in cfg.sa:
        chans = [c_in] + list(sa.mlp)
        params["sa"].append(nn.mlp_init(next(keys), chans))
        c_in = sa.mlp[-1] + 3  # next stage consumes features + xyz
    sa_out = cfg.sa[-1].mlp[-1]

    if cfg.task == "cls":
        params["global"] = nn.mlp_init(next(keys), [sa_out + 3] + list(cfg.global_mlp))
        h = [cfg.global_mlp[-1]] + list(cfg.head) + [cfg.n_classes]
        params["head"] = nn.mlp_init(next(keys), h, norm=False)
    else:
        # FP stages walk back up the SA pyramid
        params["fp"] = []
        skips = [3 + cfg.in_features] + [sa.mlp[-1] for sa in cfg.sa[:-1]]
        c_coarse = sa_out
        for i, skip_c in enumerate(reversed(skips)):
            cout = cfg.fp_mlp[min(i, len(cfg.fp_mlp) - 1)]
            params["fp"].append(nn.mlp_init(next(keys), [c_coarse + skip_c, cout, cout]))
            c_coarse = cout
        h = [c_coarse] + list(cfg.head) + [cfg.n_classes]
        params["head"] = nn.mlp_init(next(keys), h, norm=False)
    return params


def stage_engine(
    cfg: PointNet2Config, sa: SAConfig, n_points: int,
    policy: ExecutionPolicy | None = None,
):
    """Batched PreprocessEngine for one SA stage (cached per distinct config).

    The policy's backend/interpret flags participate in the engine identity,
    so preprocessing and the SC feature path always run under the SAME
    backend decision (the old API let them drift apart).  A policy with
    backend=None defers to the config's pinned preproc_backend."""
    policy = resolve_policy(cfg, policy)
    backend = policy.backend
    if cfg.preproc == "pc2im":
        ec = EngineConfig(
            pipeline="pc2im",
            n_centroids=sa.n_centroids,
            radius=sa.radius,
            nsample=sa.nsample,
            depth=clamp_depth(n_points, sa.n_centroids, cfg.msp_depth),
            backend=backend,
            interpret=policy.interpret,
        )
    else:
        ec = EngineConfig(
            pipeline=cfg.preproc,
            n_centroids=sa.n_centroids,
            radius=sa.radius,
            nsample=sa.nsample,
            backend=backend,
            interpret=policy.interpret,
        )
    return get_engine(ec)


def preprocess_stage(
    cfg: PointNet2Config, points: jax.Array,
    policy: ExecutionPolicy | None = None,
) -> tuple:
    """Params-free preprocessing half: points (B, N, 3+F) -> per-SA results.

    The whole preprocessing chain — MSP partition, FPS, lattice/ball query,
    stage after stage — consumes only coordinates: stage i samples from
    stage i-1's *centroid_xyz*, never from learned features.  That is the
    paper's decoupling (and Mesorasi's delayed-aggregation observation)
    made explicit: this function is the "preprocess sub-artifact" the
    pipelined accelerator runs for micro-batch k+1 while micro-batch k is
    still inside the feature MLPs.  Returns one PreprocessResult per SA
    stage; feed them to `feature_stage` to finish the forward pass.
    """
    policy = resolve_policy(cfg, policy)
    xyz = points[..., :3]
    # under an enclosing jit (the accelerator's sub-artifact), the raw engine
    # pipelines trace into ONE jaxpr; eager callers (e.g. un-jitted loss_fn
    # under jax.grad-less loops) keep each stage's own compiled engine
    traced = isinstance(xyz, jax.core.Tracer)
    results = []
    for i, sa_cfg in enumerate(cfg.sa, 1):
        engine = stage_engine(cfg, sa_cfg, xyz.shape[-2], policy)
        with jax.named_scope(f"sa{i}"):
            res = engine.raw(xyz) if traced else engine(xyz)
        results.append(res)
        xyz = res.centroid_xyz
    return tuple(results)


def feature_stage(
    params, cfg: PointNet2Config, points: jax.Array, preproc: tuple,
    policy: ExecutionPolicy | None = None,
) -> jax.Array:
    """Feature half: per-point MLPs + aggregation over precomputed neighborhoods.

    `preproc` is `preprocess_stage`'s output (one PreprocessResult per SA
    stage).  Composing the two stages is bitwise-identical to the fused
    forward — `forward` IS this composition — which is what lets the
    pipelined executor overlap the halves of consecutive micro-batches
    without changing a single output bit (pinned by
    tests/test_pipelined_accelerator.py).
    """
    policy = resolve_policy(cfg, policy)
    xyz = points[..., :3]
    feats = points[..., 3:] if cfg.in_features else None

    levels = [(xyz, feats)]
    for i, (sa_cfg, mlp_p, res) in enumerate(zip(cfg.sa, params["sa"], preproc), 1):
        xyz_i, feats_i = levels[-1]
        with jax.named_scope(f"sa{i}"):
            levels.append(_sa_stage(cfg, sa_cfg, mlp_p, xyz_i, feats_i, policy, res=res))

    if cfg.task == "cls":
        xyz_l, feats_l = levels[-1]
        with jax.named_scope("global"):
            with jax.named_scope("mlp"):
                x = jnp.concatenate([xyz_l, feats_l], axis=-1)  # (B, M, C)
                x = nn.mlp_apply(params["global"], x, policy=policy)
            with jax.named_scope("pool"):
                x = jnp.max(x, axis=1)  # global max pool per cloud
        with jax.named_scope("head"):
            return nn.mlp_apply(params["head"], x, final_act=False, policy=policy)

    # segmentation: FP stages walk the pyramid back from coarse to fine.
    # Skip channels (mirrors init_params): intermediate levels contribute
    # their SA features; the finest level contributes raw xyz(+input feats).
    coarse_xyz, coarse_f = levels[-1]
    n_fp = len(params["fp"])
    for i, fp_p in enumerate(params["fp"]):
        fine_xyz, fine_f = levels[n_fp - 1 - i]
        with jax.named_scope(f"fp{i + 1}"):
            with jax.named_scope("knn"):
                idx, dist = fp_knn(fine_xyz, coarse_xyz, policy)
            with jax.named_scope("interp"):
                w = Q.three_nn_interpolate_weights(dist)
                interp = jax.vmap(G.interpolate_features)(coarse_f, idx, w)  # (B, Nf, Cc)
                if i == n_fp - 1:  # finest level: raw inputs as skip
                    skip = fine_xyz if fine_f is None else jnp.concatenate([fine_xyz, fine_f], -1)
                else:
                    skip = fine_f
                x = jnp.concatenate([interp, skip], axis=-1)
            with jax.named_scope("mlp"):
                coarse_f = nn.mlp_apply(fp_p, x, policy=policy)
        coarse_xyz = fine_xyz
    with jax.named_scope("head"):
        return nn.mlp_apply(params["head"], coarse_f, final_act=False, policy=policy)


def fp_knn(fine_xyz: jax.Array, coarse_xyz: jax.Array, policy: ExecutionPolicy):
    """3 nearest coarse points of every fine point: (B, Nf, 3), (B, Nc, 3) -> (idx, dist).

    Runs the fused `knn3` kernel under the policy's backend: on the TPU one
    `pc2im_knn3` call per FP stage, with the batch as a grid axis; off it
    `core.query.knn`.  Both give the same bits (squared L2 distances summed
    in one order, first index on ties).
    """
    return jax.vmap(
        lambda q, r: knn3(q, r, backend=policy.backend, interpret=policy.interpret)
    )(fine_xyz, coarse_xyz)


def _sa_stage(cfg, sa_cfg, mlp_params, xyz, feats, policy, res=None):
    """One BATCHED set-abstraction stage.  xyz (B, N, 3), feats (B, N, C)|None.

    Preprocessing runs through the PreprocessEngine (batch and MSP tiles fold
    into one kernel grid); the per-point MLP applies batch-wide (it is
    leading-dim agnostic); only the index gathers vmap over clouds.  Passing
    a precomputed `res` (from `preprocess_stage`) skips the engine call —
    the feature-stage sub-artifact consumes neighborhoods computed earlier.
    """
    if res is None:
        res = stage_engine(cfg, sa_cfg, xyz.shape[-2], policy)(xyz)
    nbrs = res.neighbors
    if cfg.aggregation == "delayed":
        # C5: per-POINT mlp on [abs-xyz, feats], then gather + masked maxpool
        with jax.named_scope("mlp"):
            x = xyz if feats is None else jnp.concatenate([xyz, feats], axis=-1)
            pointwise = nn.mlp_apply(mlp_params, x, policy=policy)  # (B, N, C')
        with jax.named_scope("group"):
            grouped = jax.vmap(G.group_features)(pointwise, nbrs)  # (B, M, S, C')
            new_feats = G.masked_maxpool(grouped, nbrs.mask)
    else:
        with jax.named_scope("group"):
            rel = jax.vmap(G.group_relative_coords)(xyz, res.centroid_xyz, nbrs)
            if feats is None:
                grouped = rel
            else:
                gf = jax.vmap(G.group_features)(feats, nbrs)  # (B, M, S, C)
                grouped = jnp.concatenate([rel, gf], axis=-1)
        with jax.named_scope("mlp"):
            pointwise = nn.mlp_apply(mlp_params, grouped, policy=policy)
        with jax.named_scope("group"):
            new_feats = G.masked_maxpool(pointwise, nbrs.mask)
    return res.centroid_xyz, new_feats


def forward(
    params, cfg: PointNet2Config, points: jax.Array,
    policy: ExecutionPolicy | None = None,
) -> jax.Array:
    """Batched forward.  points: (B, N, 3+F) -> (B, C) or (B, N, C).

    policy=None derives the config's default ExecutionPolicy; pass one
    explicitly (or use core.accelerator.PC2IMAccelerator) to select the
    quant mode / kernel backend without touching the config.  Resolution
    happens HERE, once: a backend=None policy picks up the config's pinned
    backend for the preprocessing engines AND the SC feature path."""
    policy = resolve_policy(cfg, policy)
    return _forward_batched(params, cfg, points, policy)


def _forward_batched(params, cfg: PointNet2Config, points: jax.Array, policy):
    """points: (B, N, 3 + in_features) -> logits (cls: (B,C), seg: (B,N,C)).

    Literally the composition of the two stage functions — the sequential
    path and the pipelined path run the SAME code, so their bitwise
    equality is true by construction, not by accident of XLA scheduling.
    """
    return feature_stage(
        params, cfg, points, preprocess_stage(cfg, points, policy), policy
    )


def loss_fn(
    params, cfg: PointNet2Config, points: jax.Array, labels: jax.Array,
    policy: ExecutionPolicy | None = None,
):
    logits = forward(params, cfg, points, policy=policy)
    logp = jax.nn.log_softmax(logits, axis=-1)
    if cfg.task == "cls":
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
    else:
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()
    acc = (jnp.argmax(logits, -1) == labels).mean()
    return nll, {"loss": nll, "accuracy": acc}
