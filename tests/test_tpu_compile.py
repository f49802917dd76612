"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode runs a kernel's body as plain JAX, so it cannot see what
Mosaic refuses: blocks that break the (8, 128) tiling rule, lane-axis
dynamic slices, scalar stores to VMEM, ops with no TPU lowering.  These
tests compile the kernels ahead of time for a `v5e:2x2` topology that is
described, not attached, at the shapes the cls and seg configs run at B=8:

  * every SA stage's PreprocessEngine (MSP partition, FPS, lattice query
    and their lane padding);
  * sc_matmul at every feature layer's (M, K, N) of cls, W16A16 and W8A8;
  * knn3 at every seg feature-propagation stage's (fine, coarse) sizes;
  * the whole served seg program, whose FP stages call knn3.

Each compiled program must call a Mosaic kernel (`tpu_custom_call`).  The
topology is described inside a fixture — never at import — because only one
process at a time may load the TPU library.
"""

import os
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import pointnet2_cls, pointnet2_seg
from repro.core.accelerator import PC2IMAccelerator
from repro.core.policy import ExecutionPolicy
from repro.kernels.knn3.ops import knn3
from repro.kernels.sc_matmul.ops import sc_matmul_op
from repro.models import pointnet2 as PN

ROOT = pathlib.Path(__file__).resolve().parents[1]
BATCH = 8
CONFIGS = {"cls": pointnet2_cls.CONFIG, "seg": pointnet2_seg.CONFIG}
COMPILED = ExecutionPolicy(backend="pallas", interpret=False)


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 topology, with the persistent compile cache off.

    A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip, so the cache stays off here.
    """
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


def _stage_cases():
    for name, cfg in CONFIGS.items():
        for i in range(len(cfg.sa)):
            yield pytest.param(name, i, id=f"{name}-sa{i}")


@pytest.mark.parametrize("config,stage", list(_stage_cases()))
def test_preprocess_engine_compiles(one_chip, config, stage):
    cfg = CONFIGS[config]
    n = cfg.n_points if stage == 0 else cfg.sa[stage - 1].n_centroids
    engine = PN.stage_engine(cfg, cfg.sa[stage], n, COMPILED)
    assert engine.config.backend == "pallas" and engine.config.interpret is False
    spec = jax.ShapeDtypeStruct((BATCH, n, 3), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(engine.raw, spec)


def _feature_layer_shapes(cfg, batch: int):
    """(M, K, N) of every linear in the cls forward at `batch` clouds."""
    params = jax.eval_shape(lambda: PN.init_params(jax.random.PRNGKey(0), cfg))
    rows = [batch * cfg.n_points] + [batch * sa.n_centroids for sa in cfg.sa]
    mlps = list(params["sa"]) + [params["global"]]
    shapes = []
    for m, mlp in zip(rows, mlps):
        shapes += [(m, *lay["lin"]["w"].shape) for lay in mlp["layers"]]
    shapes += [(batch, *lay["lin"]["w"].shape) for lay in params["head"]["layers"]]
    return shapes


@pytest.mark.parametrize("bits", [16, 8])
@pytest.mark.parametrize(
    "m,k,n", _feature_layer_shapes(pointnet2_cls.CONFIG, BATCH), ids=str
)
def test_sc_matmul_compiles(one_chip, m, k, n, bits):
    x = jax.ShapeDtypeStruct((m, k), jnp.int32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((k, n), jnp.int32, sharding=one_chip)
    fn = lambda a, b: sc_matmul_op(a, b, bits=bits, backend="pallas", interpret=False)  # noqa: E731
    assert "tpu_custom_call" in _compiled_text(fn, x, w)


def _fp_cases():
    """(fine, coarse) point counts of each seg FP stage: level i+1 -> level i."""
    cfg = CONFIGS["seg"]
    sizes = [cfg.n_points] + [sa.n_centroids for sa in cfg.sa]
    for i in range(len(cfg.sa)):
        yield pytest.param(sizes[i], sizes[i + 1], id=f"seg-fp{i}")


@pytest.mark.parametrize("n_fine,n_coarse", list(_fp_cases()))
def test_knn3_compiles(one_chip, n_fine, n_coarse):
    q = jax.ShapeDtypeStruct((BATCH, n_fine, 3), jnp.float32, sharding=one_chip)
    p = jax.ShapeDtypeStruct((BATCH, n_coarse, 3), jnp.float32, sharding=one_chip)
    fn = jax.vmap(lambda a, b: knn3(a, b, backend="pallas", interpret=False))
    assert "tpu_custom_call" in _compiled_text(fn, q, p)


def test_seg_program_calls_knn3_under_fp_knn_scopes(one_chip):
    """Each FP stage's 3-NN is one `pc2im_knn3` call, mapped to its `fp{i}/knn` scope.

    The map is `bench/benchlib/scopes.hlo_op_scopes`, the one the
    benchmark's `knn_ms` reads, so that metric keeps measuring the layer.
    """
    sys.path.insert(0, str(ROOT / "bench"))
    from benchlib.scopes import hlo_op_scopes

    cfg = CONFIGS["seg"]
    accel = PC2IMAccelerator(cfg, COMPILED)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(accel.init, jax.random.PRNGKey(0)),
    )
    spec = jax.ShapeDtypeStruct((BATCH, cfg.n_points, 3), jnp.float32, sharding=one_chip)
    text = accel.infer_program.lower(params, spec).compile().as_text()
    calls = re.findall(
        r'^\s*(?:ROOT\s+)?%(pc2im_knn3[\w.]*) = .*custom_call_target="tpu_custom_call"',
        text, re.MULTILINE,
    )
    scopes = hlo_op_scopes(text)
    stages = sorted(scopes[c].split("/")[:2] for c in calls)
    assert stages == [[f"fp{i}", "knn"] for i in range(1, len(cfg.sa) + 1)]
