"""BENCHMARK.json resolves to its files, and new files are found by name."""

import json
import pathlib
import re
import shutil
import subprocess
import sys
from types import SimpleNamespace

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

from benchlib import cell, work  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_command():
    """The file has the contract's keys and a command inside the repo."""
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert all(not w.startswith("/") and ".." not in w for w in SPEC["command"])
    assert (ROOT / SPEC["command"][1]).is_file()
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_workload_resolves(w):
    """Each workload finds its configuration, mix and metric readers."""
    c = cell.load_cell(w["name"])
    assert c.chips in (1, 4)
    assert c.mix["loop"] == "closed"
    assert {"setup_s"} < {m["name"] for m in c.end_to_end}
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert callable(cell.load_reader(m["name"]))
    assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and len(w["why"]) <= 200


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file(c):
    """Each configuration file agrees with its entry and names its reference and program."""
    cfg = json.loads((ROOT / c["file"]).read_text())
    assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
    assert cfg["reduced"] == c["reduced"]
    assert (ROOT / "bench" / "reference" / f"{cfg['reference']}.py").is_file()
    assert (ROOT / "bench" / "programs" / f"{cfg['program']}.py").is_file()
    assert 0 < cfg["compare"]["limit"] < 1
    assert any(w["config"] == c["name"] for w in SPEC["workloads"])


def test_metric_entries():
    """Metric names, units, bounds, sources and cell lists keep the contract."""
    names = set()
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in names
        names.add(m["name"])
        assert set(m.get("workloads", [])) <= cells
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


def test_new_cell_metric_and_mix_found_without_edits(tmp_path):
    """Added files are found by name with no existing file edited."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "traffic" / "tiny-closed2.json").write_text(json.dumps(
        {"loop": "closed", "clients": 2, "sizes": {"kind": "fixed", "points": 1024}, "pool": 4}))
    (tmp_path / "bench" / "metrics" / "clouds_seen.py").write_text(
        '"""Test reader."""\n\n\ndef read(ctx):\n    """Count."""\n    return 7.0\n')
    spec["workloads"].append({"name": "cls-tiny", "config": "pointnet2-cls-w16a16",
                              "traffic": "tiny-closed2", "chips": 1, "why": "test"})
    spec["end_to_end"][0]["workloads"].append("cls-tiny")
    spec["per_layer"].append({"name": "clouds_seen", "unit": "clouds", "better": "higher",
                              "source": "program_counter", "layer": "serve.dispatch",
                              "moves": "clouds_per_s", "workloads": ["cls-tiny"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    c = cell.load_cell("cls-tiny", tmp_path)
    assert c.mix["clients"] == 2
    assert [m["name"] for m in c.per_layer] == ["clouds_seen"]
    assert cell.load_reader("clouds_seen", tmp_path)(None) == 7.0


# A configuration of another program: its adapter, reference, traffic with
# colour channels and cell are new files. The test's adapter and reference
# serve PointNet++ on xyz + rgb through the files already there.
ADAPTER = '''"""Test adapter: PointNet++ on xyz and colour."""

import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "pn2_base", pathlib.Path(__file__).with_name("pointnet2.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)


def config(model):
    """The program's configuration."""
    return _base.config(dict(model, in_features=model["colour"]))


def linear_shapes(model):
    """Every linear of one cloud."""
    return _base.linear_shapes(dict(model, in_features=model["colour"]))
'''
REFERENCE = '''"""Test reference: PointNet++ on xyz and colour."""

import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "pn2_ref", pathlib.Path(__file__).with_name("pointnet2.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)
fit_rows, output_rows = _base.fit_rows, _base.output_rows


def init_params(key, model):
    """Seeded weights."""
    return _base.init_params(key, dict(model, in_features=model["colour"]))


def make_block_fn(model, **kw):
    """The reference over a block of clouds."""
    return _base.make_block_fn(dict(model, in_features=model["colour"]), **kw)
'''
RGB_MODEL = {
    "name": "pn2-rgb", "task": "seg", "n_points": 256, "n_classes": 13, "colour": 3,
    "sa": [{"n_centroids": 64, "radius": 0.3, "nsample": 16, "mlp": [32, 32, 64]},
           {"n_centroids": 16, "radius": 0.6, "nsample": 16, "mlp": [64, 64, 128]}],
    "fp_mlp": [64, 64], "head": [64], "preproc": "pc2im", "aggregation": "delayed",
    "msp_depth": 2,
}


@pytest.fixture(scope="module")
def rgb_root(tmp_path_factory):
    """A checkout with a third configuration, its cell, mix and files added."""
    root = tmp_path_factory.mktemp("rgb")
    shutil.copytree(ROOT / "bench", root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench" / "programs" / "pn2_rgb.py").write_text(ADAPTER)
    (root / "bench" / "reference" / "pn2_rgb.py").write_text(REFERENCE)
    (root / "bench" / "traffic" / "tiny-rgb3.json").write_text(json.dumps(
        {"loop": "closed", "clients": 4, "sizes": {"kind": "fixed", "points": 256}, "pool": 8,
         "features": 3}))
    (root / "bench" / "configs" / "pn2-rgb.json").write_text(json.dumps({
        "name": "pn2-rgb", "source": "test", "program": "pn2_rgb", "reference": "pn2_rgb",
        "model": RGB_MODEL, "policy": {"quant": "none"}, "matmul_precision": "highest",
        "compare": {"sample": 4, "block": 4, "limit": 6e-6},
        "control": {"reference_passes": 3}, "reduced": []}))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "pn2-rgb", "source": "test", "reduced": [], "why": "test",
                            "file": "bench/configs/pn2-rgb.json"})
    spec["workloads"].append({"name": "seg-rgb-tiny", "config": "pn2-rgb",
                              "traffic": "tiny-rgb3", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in ("clouds_per_s", "step_mfu"):
            m["workloads"].append("seg-rgb-tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    yield root
    jax.config.update("jax_default_matmul_precision", None)


def _run_rgb(root):
    result = cell.run("seg-rgb-tiny", 2**33 + 5, 1.0, False, require_chip=False,
                      policy_override={"backend": "xla"}, compile_cache=False, root=root)
    for path in (ROOT / "bench").rglob("*"):  # every copied file byte-identical
        if path.is_file() and "__pycache__" not in path.parts:
            assert (root / path.relative_to(ROOT)).read_bytes() == path.read_bytes(), path
    return result


def test_new_architecture_enters_as_new_files(rgb_root):
    """The added configuration serves xyz + rgb correctly; step_mfu counts its adapter's linears."""
    c = cell.load_cell("seg-rgb-tiny", rgb_root)
    assert pathlib.Path(c.program.__file__) == rgb_root / "bench" / "programs" / "pn2_rgb.py"
    assert {m["name"] for m in c.per_layer} == {"step_mfu"}
    r = _run_rgb(rgb_root)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["checks"]["checked"]["value"] >= 1
    linears = c.program.linear_shapes(RGB_MODEL)
    assert linears[0] == (256, 6, 32)
    ctx = SimpleNamespace(program=c.program, model=RGB_MODEL, chips=1, clouds_per_s=10.0,
                          peaks={"bf16_flops_per_s": 1e12})
    mfu = cell.load_reader("step_mfu", rgb_root)(ctx)
    assert mfu == pytest.approx(100.0 * work.model_flops_per_cloud(linears) * 10.0 / 1e12)


def test_new_architecture_with_features_zeroed_reads_incorrect(rgb_root, monkeypatch):
    """Served colour channels zeroed before the model: the comparison fails."""
    from repro.core.accelerator import PC2IMAccelerator

    orig = PC2IMAccelerator.infer

    def infer(self, params, points):
        return orig(self, params, points.at[..., 3:].set(0.0))

    monkeypatch.setattr(PC2IMAccelerator, "infer", infer)
    r = _run_rgb(rgb_root)
    assert not r["correct"]
    assert r["checks"]["logit_gap"]["value"] > r["checks"]["logit_gap"]["limit"]


def _run_cli(cwd, *args):
    env = {"PATH": "/usr/bin:/bin:/usr/local/bin", "JAX_PLATFORMS": "cpu",
           "HOME": str(cwd)}
    return subprocess.run(
        [sys.executable, "bench/run_cell.py", "--workload", "cls-modelnet-closed",
         "--seed", "3", "--seconds", "1", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_command_refuses_a_machine_without_tpu():
    """Without a TPU the command exits nonzero and prints no result."""
    out = _run_cli(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs 1 TPU chip" in out.stderr


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    """A checkout of only the benchmark files exits nonzero."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    out = _run_cli(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
