"""The cell runner at smoke size on the CPU: generator, loop, check.

The chip check is skipped (`require_chip=False`) and the model cut to the
program's smoke sizes; everything else is the run the benchmark makes. The
fault cases break the timed path underneath and must read `correct` false.
"""

import pathlib
import sys

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

from benchlib import cell, traffic  # noqa: E402
from repro.core.accelerator import PC2IMAccelerator  # noqa: E402

SA = [{"n_centroids": 64, "radius": 0.3, "nsample": 16, "mlp": [32, 32, 64]},
      {"n_centroids": 16, "radius": 0.6, "nsample": 16, "mlp": [64, 64, 128]}]
# four levels like the seg configuration: SA2 runs on one tile, SA3 and SA4
# on tiles smaller than nsample
SA4 = [{"n_centroids": 64, "radius": 0.2, "nsample": 16, "mlp": [16, 16, 32]},
       {"n_centroids": 32, "radius": 0.4, "nsample": 16, "mlp": [32, 32, 64]},
       {"n_centroids": 8, "radius": 0.6, "nsample": 16, "mlp": [64, 64, 128]},
       {"n_centroids": 2, "radius": 0.8, "nsample": 16, "mlp": [128, 128, 256]}]
SMOKE = {
    "cls": {"n_points": 256, "sa": SA, "global_mlp": [128, 256], "head": [128], "msp_depth": 2},
    "seg": {"n_points": 256, "sa": SA4, "fp_mlp": [64, 64, 64, 32], "head": [32], "msp_depth": 3},
}
XLA = {"backend": "xla"}
INTERPRET = {"backend": "pallas", "interpret": True}


@pytest.fixture(autouse=True)
def _restore_precision():
    """Undo the matmul precision a seg run sets for the process."""
    yield
    jax.config.update("jax_default_matmul_precision", None)


def _run(workload, seed=2**33 + 17, seconds=1.0, trace=False, policy=XLA, **kw):
    task = "seg" if workload.startswith("seg") else "cls"
    return cell.run(workload, seed, seconds, trace, require_chip=False,
                    model_override=SMOKE[task], policy_override=policy,
                    compile_cache=False, **kw)


def test_closed_cls_run_in_interpret_mode_is_correct():
    """A closed-loop run through the Pallas kernels in interpret mode is correct."""
    r = _run("cls-modelnet-closed", policy=INTERPRET)
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    assert r["failed"] == 0 and r["attempted"] >= 32
    assert set(r["metrics"]) == {"clouds_per_s", "setup_s"}
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1


@pytest.mark.parametrize("workload", ["cls-modelnet-closed", "seg-s3dis-closed"])
def test_control_fails_the_comparison(workload):
    """The control of each configuration reads above the limit."""
    r = _run(workload, control=True)
    gap = r["checks"]["logit_gap"]
    assert not r["correct"] and gap["value"] > gap["limit"]


def _altered_answer(orig):
    def infer(self, params, points):
        return orig(self, params, points) * 1.01
    return infer


def _half_batch_left_out(orig):
    def infer(self, params, points):
        half = points.shape[0] // 2
        return orig(self, params, points.at[half:].set(0.0))
    return infer


@pytest.mark.parametrize("fault", [_altered_answer, _half_batch_left_out],
                         ids=["answer-altered", "half-batch-left-out"])
def test_faults_on_the_timed_path_read_incorrect(monkeypatch, fault):
    """A fault planted under the timed path makes `correct` false."""
    monkeypatch.setattr(PC2IMAccelerator, "infer", fault(PC2IMAccelerator.infer))
    r = _run("cls-modelnet-closed")
    assert not r["correct"]
    assert r["checks"]["logit_gap"]["value"] > r["checks"]["logit_gap"]["limit"]


def test_sizes_are_the_same_set_for_every_seed():
    """Seeds reorder one fixed set of sizes."""
    mix = {"sizes": {"kind": "loguniform", "low": 512, "high": 2048}, "pool": 64}
    a = traffic.cloud_sizes(mix["sizes"], 64)
    assert a.min() >= 512 and a.max() <= 2048
    assert (a < 1024).sum() == (a > 1024).sum()
    assert (traffic.cloud_sizes({"kind": "fixed", "points": 1024}, 8) == 1024).all()
    s1 = [len(c) for c in traffic.make_pool(dict(mix, pool=8), 1)]
    s2 = [len(c) for c in traffic.make_pool(dict(mix, pool=8), 2**40 + 3)]
    assert sorted(s1) == sorted(s2) and s1 != s2


def test_pool_is_made_from_the_seed():
    """The same seed makes the same clouds; another seed others."""
    mix = {"sizes": {"kind": "loguniform", "low": 4, "high": 16}, "pool": 6}
    p1, p2 = traffic.make_pool(mix, 5), traffic.make_pool(mix, 5)
    assert all(np.array_equal(a, b) for a, b in zip(p1, p2))
    assert sorted(len(c) for c in p1) == sorted(traffic.cloud_sizes(mix["sizes"], 6))
    p3 = traffic.make_pool(mix, 2**35 + 5)
    assert not all(len(a) == len(b) and np.array_equal(a, b) for a, b in zip(p1, p3))
