"""The FP stages' 3-NN runs on the fused knn3 kernel and keeps core.query.knn's bits.

`models.pointnet2.fp_knn` is the 3-NN search of every feature-propagation
stage.  Under a Pallas policy it is one `pc2im_knn3` call per stage, with
the batch as a grid axis (here in interpret mode); under the XLA policy it
is `core.query.knn` under vmap.  Both sides are compiled, as the model runs
them: inside a fusion the CPU compiler may contract a multiply and an add
into one rounding, so `knn` run op by op is not the reference here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.configs import pointnet2_seg
from repro.core import query as Q
from repro.core.accelerator import get_accelerator
from repro.core.policy import ExecutionPolicy
from repro.models import pointnet2 as PN

jax.config.update("jax_platform_name", "cpu")

BATCH = 2
SEG = pointnet2_seg.smoke_config()
PALLAS = ExecutionPolicy(backend="pallas", interpret=True)
XLA = ExecutionPolicy(backend="xla")


def _fp_sizes(cfg):
    """(fine, coarse) point counts of each FP stage, coarsest (fp1) first."""
    sizes = [cfg.n_points] + [sa.n_centroids for sa in cfg.sa]
    return [(sizes[i - 1], sizes[i]) for i in range(len(sizes) - 1, 0, -1)]


def _clouds(kind: str, n_fine: int, n_coarse: int, seed: int):
    kf, kc = jax.random.split(jax.random.PRNGKey(seed))
    fine = jax.random.uniform(kf, (BATCH, n_fine, 3), minval=-1.0, maxval=1.0)
    coarse = jax.random.uniform(kc, (BATCH, n_coarse, 3), minval=-1.0, maxval=1.0)
    if kind == "duplicates":  # every coarse point twice: equal distances, first index wins
        coarse = jnp.repeat(coarse[:, : n_coarse // 2], 2, axis=1)
    elif kind == "lattice":  # integer coordinates: many exactly equal distances
        fine, coarse = jnp.round(2 * fine), jnp.round(2 * coarse)
    return fine, coarse


_jit_fp_knn = jax.jit(PN.fp_knn, static_argnums=2)


@pytest.mark.parametrize("kind", ["uniform", "duplicates", "lattice"])
@pytest.mark.parametrize(
    "n_fine,n_coarse", _fp_sizes(SEG), ids=[f"fp{i}" for i in range(1, len(SEG.sa) + 1)]
)
def test_fp_knn_kernel_is_bitwise_core_knn(kind, n_fine, n_coarse):
    fine, coarse = _clouds(kind, n_fine, n_coarse, seed=n_fine + n_coarse)
    idx, dist = _jit_fp_knn(fine, coarse, PALLAS)
    ref_idx, ref_dist = jax.jit(jax.vmap(lambda q, r: Q.knn(q, r, 3)))(fine, coarse)
    assert idx.shape == dist.shape == (BATCH, n_fine, 3)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))
    np.testing.assert_array_equal(np.asarray(dist), np.asarray(ref_dist))
    xla_idx, xla_dist = _jit_fp_knn(fine, coarse, XLA)
    np.testing.assert_array_equal(np.asarray(xla_idx), np.asarray(ref_idx))
    np.testing.assert_array_equal(np.asarray(xla_dist), np.asarray(ref_dist))


def test_fp_knn_pads_the_coarse_level_to_the_lane_width():
    """fp1's 16 reference points are padded to 128; no pad point is ever chosen."""
    n_fine, n_coarse = _fp_sizes(SEG)[0]
    assert n_coarse % 128 != 0
    fine, coarse = _clouds("uniform", n_fine, n_coarse, seed=0)
    idx, dist = _jit_fp_knn(fine, coarse, PALLAS)
    assert int(idx.max()) < n_coarse and bool(jnp.all(dist <= 12.0))


def _knn3_calls(jaxpr) -> list:
    """Every `pc2im_knn3` pallas_call equation of a jaxpr, nested jaxprs included."""
    calls = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and "pc2im_knn3" in str(
            eqn.params["name"]
        ):
            calls.append(eqn)
        for v in eqn.params.values():
            if isinstance(v, ClosedJaxpr):
                calls += _knn3_calls(v.jaxpr)
            elif isinstance(v, Jaxpr):
                calls += _knn3_calls(v)
    return calls


def test_seg_forward_makes_one_knn3_call_per_fp_stage():
    accel = get_accelerator(SEG, PALLAS)
    params = jax.eval_shape(accel.init, jax.random.PRNGKey(0))
    points = jax.ShapeDtypeStruct((BATCH, SEG.n_points, 3), jnp.float32)
    calls = _knn3_calls(jax.make_jaxpr(accel.infer)(params, points).jaxpr)
    assert len(calls) == len(SEG.sa)
    for eqn in calls:  # the batch is the kernel's leading grid axis, not B launches
        assert eqn.params["grid_mapping"].grid[0] == BATCH


def test_seg_forward_on_the_kernel_equals_the_xla_logits():
    params = get_accelerator(SEG).init(jax.random.PRNGKey(0))
    points = jax.random.uniform(
        jax.random.PRNGKey(1), (BATCH, SEG.n_points, 3), minval=-1.0, maxval=1.0
    )
    got = get_accelerator(SEG, PALLAS).infer(params, points)
    want = get_accelerator(SEG, XLA).infer(params, points)
    assert got.dtype == want.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
