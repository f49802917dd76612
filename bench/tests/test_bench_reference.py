"""The plain reference against the program on the CPU at smoke size."""

import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

from benchlib import cell, correct, shapes  # noqa: E402
from repro.configs import pointnet2_cls, pointnet2_seg  # noqa: E402
from repro.core.accelerator import get_accelerator  # noqa: E402
from repro.core.policy import ExecutionPolicy  # noqa: E402
from repro.serve.pointcloud import inverse_subsample_indices, pad_cloud  # noqa: E402

REF = cell.load_reference("pointnet2")
PN2 = cell.load_program("pointnet2")


def _model(cfg, task):
    sa = [{"n_centroids": s.n_centroids, "radius": s.radius, "nsample": s.nsample,
           "mlp": list(s.mlp)} for s in cfg.sa]
    return {"task": task, "n_points": cfg.n_points, "n_classes": cfg.n_classes, "sa": sa,
            "global_mlp": list(cfg.global_mlp), "fp_mlp": list(cfg.fp_mlp),
            "head": list(cfg.head), "msp_depth": cfg.msp_depth, "in_features": cfg.in_features}


# (task, quant, tolerance, per-point features beyond xyz)
CASES = [
    ("cls", "none", 1e-5, 0),
    ("cls", "sc_w16a16", 1e-3, 0),
    ("seg", "none", 1e-5, 0),
    ("cls", "none", 1e-5, 3),
    ("seg", "none", 1e-5, 3),
]


@pytest.mark.parametrize("task,quant,tol,features", CASES,
                         ids=[f"{t}-{q}" + (f"-f{f}" if f else "") for t, q, _, f in CASES])
def test_reference_matches_program(task, quant, tol, features):
    """Served logits of a smoke batch, (n, 3 + features) clouds, lie within the tolerance."""
    cfg = (pointnet2_cls if task == "cls" else pointnet2_seg).smoke_config()
    cfg = dataclasses.replace(cfg, in_features=features)
    model = _model(cfg, task)
    params = REF.init_params(jax.random.PRNGKey(1), model)
    pts = np.asarray(shapes.clouds(jax.random.PRNGKey(2), 4, cfg.n_points))
    if features:
        colour = shapes.features(jax.random.PRNGKey(3), 4, cfg.n_points, features)
        pts = np.concatenate([pts, np.asarray(colour)], -1)
    accel = get_accelerator(cfg, ExecutionPolicy(quant=quant, backend="xla"))
    served = np.asarray(accel.infer(params, jnp.asarray(pts)))
    ref = np.asarray(REF.make_block_fn(model, quant=quant)(params, pts))
    for i in range(len(pts)):
        assert correct.gap(served[i], ref[i]) < tol
    if features:  # the features reach the logits: zeroed, the reference moves far
        no_colour = pts.copy()
        no_colour[..., 3:] = 0.0
        moved = np.asarray(REF.make_block_fn(model, quant=quant)(params, no_colour))
        assert min(correct.gap(moved[i], ref[i]) for i in range(len(pts))) > 100 * tol


def test_reference_preprocess_matches_program_bitwise():
    """Centroids, neighbours and masks equal the program's bit for bit."""
    cfg = pointnet2_seg.smoke_config()
    model = _model(cfg, "seg")
    pts = np.asarray(shapes.clouds(jax.random.PRNGKey(3), 2, cfg.n_points))
    pre = get_accelerator(cfg, ExecutionPolicy(backend="xla")).preprocess_stage(jnp.asarray(pts))
    for b in range(2):
        xyz = pts[b]
        for sa, res in zip(model["sa"], pre):
            cidx, nidx, mask = REF.sa_preprocess(jnp.asarray(xyz), sa, model["msp_depth"])
            np.testing.assert_array_equal(cidx, res.centroid_idx[b])
            np.testing.assert_array_equal(nidx, res.neighbors.idx[b])
            np.testing.assert_array_equal(mask, res.neighbors.mask[b])
            xyz = xyz[np.asarray(cidx)]


# four levels like the seg configuration: SA2 samples its level on one tile,
# SA3 and SA4 query tiles smaller than nsample
SEG4 = {
    "name": "seg4", "task": "seg", "n_points": 256, "n_classes": 13,
    "sa": [{"n_centroids": 64, "radius": 0.2, "nsample": 16, "mlp": [16, 16, 32]},
           {"n_centroids": 32, "radius": 0.4, "nsample": 16, "mlp": [32, 32, 64]},
           {"n_centroids": 8, "radius": 0.6, "nsample": 16, "mlp": [64, 64, 128]},
           {"n_centroids": 2, "radius": 0.8, "nsample": 16, "mlp": [128, 128, 256]}],
    "fp_mlp": [64, 64, 64, 32], "head": [32], "preproc": "pc2im", "aggregation": "delayed",
    "msp_depth": 3,
}


def test_four_level_seg_matches_program():
    """Four SA levels: logits within 1e-5 of the program's, preprocessing bit for bit."""
    cfg = PN2.config(SEG4)
    assert [REF.msp_depth(n, sa["n_centroids"], 3) for n, sa in
            zip([256, 64, 32, 8], SEG4["sa"])] == [3, 0, 3, 1]
    params = REF.init_params(jax.random.PRNGKey(6), SEG4)
    pts = np.asarray(shapes.clouds(jax.random.PRNGKey(7), 2, 256))
    accel = get_accelerator(cfg, ExecutionPolicy(backend="xla"))
    served = np.asarray(accel.infer(params, jnp.asarray(pts)))
    ref = np.asarray(REF.make_block_fn(SEG4, quant="none")(params, pts))
    assert max(correct.gap(served[i], ref[i]) for i in range(2)) < 1e-5
    pre = accel.preprocess_stage(jnp.asarray(pts))
    xyz = pts[0]
    for sa, res in zip(SEG4["sa"], pre):
        cidx, nidx, mask = REF.sa_preprocess(jnp.asarray(xyz), sa, 3)
        np.testing.assert_array_equal(cidx, res.centroid_idx[0])
        np.testing.assert_array_equal(nidx, res.neighbors.idx[0])
        np.testing.assert_array_equal(mask, res.neighbors.mask[0])
        xyz = xyz[np.asarray(cidx)]


@pytest.mark.parametrize("n", [1, 5, 256, 300, 1000])
def test_fit_and_output_rows_follow_the_serving_fit(n):
    """The reference pads, subsamples and maps rows back as serving does."""
    cloud = np.random.default_rng(n).normal(size=(n, 3)).astype(np.float32)
    fitted, _ = pad_cloud(cloud, 256)
    np.testing.assert_array_equal(cloud[REF.fit_rows(n, 256)], fitted)
    if n > 256:
        np.testing.assert_array_equal(REF.output_rows(n, 256), inverse_subsample_indices(n, 256))


def test_three_pass_control_is_coarser_than_highest():
    """Three bf16 passes differ from six; other pass counts are refused."""
    model = _model(pointnet2_seg.smoke_config(), "seg")
    params = REF.init_params(jax.random.PRNGKey(4), model)
    pts = np.asarray(shapes.clouds(jax.random.PRNGKey(5), 2, model["n_points"]))
    hi = np.asarray(REF.make_block_fn(model, quant="none", passes=6)(params, pts))
    lo = np.asarray(REF.make_block_fn(model, quant="none", passes=3)(params, pts))
    assert max(correct.gap(lo[i], hi[i]) for i in range(2)) > 1e-6
    with pytest.raises(ValueError):
        REF.matmul(jnp.ones((2, 2)), jnp.ones((2, 2)), 1)

