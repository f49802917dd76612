"""chip_smoke.py off the chip: it refuses the CPU, and its phases hold here.

The script's checks run on the TPU; these tests rehearse its phase
functions at smoke size on the CPU, with the Pallas kernels in interpret
mode, so a broken phase shows up before a chip run does.
"""

import importlib.util
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np

from repro.configs import pointnet2_cls, pointnet2_seg
from repro.core.accelerator import get_accelerator
from repro.core.policy import ExecutionPolicy
from repro.serve.runtime import RuntimeConfig

ROOT = pathlib.Path(__file__).resolve().parents[1]
INTERPRETED = dict(backend="pallas", interpret=True)


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_cpu_without_ok_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "needs a TPU" in res.stderr


def test_phases_at_smoke_size():
    cs = _load_chip_smoke()
    cfg = pointnet2_cls.smoke_config()
    params = jax.jit(get_accelerator(cfg).init)(jax.random.PRNGKey(0))
    policies = (ExecutionPolicy(**INTERPRETED), ExecutionPolicy(quant=cs.SC, **INTERPRETED))
    rng = np.random.default_rng(0)
    clouds = [rng.uniform(-1, 1, (n, 3)).astype(np.float32) for n in (200, 256, 300) * 2]
    res = cs.serve_phase(cfg, params, policies, clouds, RuntimeConfig(max_batch=4, n_replicas=1))
    assert res["log"].batches
    cs.preprocess_phase(cfg, res["log"])
    results = cs.replay_phase(
        cfg, params, res["log"], lambda pol: get_accelerator(cfg, cs.xla_twin(pol))
    )
    assert set(results) == {"none", cs.SC}
    assert all(err <= cs.REL_TOL for err, _ in results.values())

    seg = pointnet2_seg.smoke_config()
    sparams = jax.jit(get_accelerator(seg).init)(jax.random.PRNGKey(1))
    batch = jax.random.uniform(jax.random.PRNGKey(2), (2, seg.n_points, 3), minval=-1, maxval=1)
    assert cs.direct_phase(seg, sparams, ExecutionPolicy(**INTERPRETED), batch) <= cs.REL_TOL


def test_fp_knn_phase_at_smoke_size():
    """The FP 3-NN check walks every FP stage of the seg pyramid and passes."""
    cs = _load_chip_smoke()
    seg = pointnet2_seg.smoke_config()
    batch = jax.random.uniform(jax.random.PRNGKey(3), (2, seg.n_points, 3), minval=-1, maxval=1)
    assert cs.fp_knn_phase(seg, ExecutionPolicy(**INTERPRETED), batch) == len(seg.sa)
    assert len(cs.SEG_S3DIS.sa) == len(cs.SEG_S3DIS.fp_mlp) == 4
