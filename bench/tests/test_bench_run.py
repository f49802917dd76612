"""The cell runner at smoke size on the CPU: generator, loop, check.

The chip check is skipped (`require_chip=False`) and the model cut to the
program's smoke sizes; everything else is the run the benchmark makes. The
fault cases break the timed path underneath and must read `correct` false.
"""

import hashlib
import json
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


# sha256 of each pool's bytes (every cloud's xyz, in pool order), taken
# before mixes could carry features; the xyz of a mix never changes
POOL_XYZ_SHA256 = {
    ("modelnet-closed32", 3): "ebecba3f88e9bdee60a9877876be3b3cf93bc88f4c3f9dc3afde478340280a38",
    ("modelnet-closed32", 2**33 + 17):
        "67b4ffc35f44597582d0e10179ff3fd16e99608123f872ccc6a0ee1315765fd6",
    ("s3dis-closed32", 3): "20a6e66a3d83203c69fc02f1db8c72969dab7407c031b668b81b1a0fc8f1fe97",
    ("s3dis-closed32", 2**33 + 17):
        "7af398b483178f7db5b9fa11fbf1a5207a1a8d946326f8aa4bea4f68628feb8d",
}


@pytest.mark.parametrize("mix,seed", sorted(POOL_XYZ_SHA256), ids=lambda v: str(v))
def test_pool_xyz_is_unchanged(mix, seed):
    """The benchmark's mixes make the same xyz, bit for bit, as before features existed."""
    spec = json.loads((ROOT / "bench" / "traffic" / f"{mix}.json").read_text())
    h = hashlib.sha256()
    for cloud in traffic.make_pool(spec, seed):
        assert cloud.shape[1] == 3
        h.update(cloud.tobytes())
    assert h.hexdigest() == POOL_XYZ_SHA256[(mix, seed)]


def test_features_come_from_their_own_key():
    """A mix's features: colour-like, in [0, 1], from the seed; its xyz as without them."""
    mix = {"sizes": {"kind": "loguniform", "low": 16, "high": 64}, "pool": 6}
    plain = traffic.make_pool(mix, 2**40 + 9)
    rgb = traffic.make_pool(dict(mix, features=3), 2**40 + 9)
    assert [c.shape for c in rgb] == [(len(c), 6) for c in plain]
    assert all(np.array_equal(a, b[:, :3]) for a, b in zip(plain, rgb))
    colour = np.concatenate([c[:, 3:] for c in rgb])
    assert colour.min() >= 0.0 and colour.max() <= 1.0 and colour.std() > 0.05
    again = traffic.make_pool(dict(mix, features=3), 2**40 + 9)
    assert all(np.array_equal(a, b) for a, b in zip(rgb, again))
