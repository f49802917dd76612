"""BENCHMARK.json resolves to its files, and new files are found by name."""

import json
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from benchlib import cell  # noqa: E402

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
    """Each configuration file agrees with its entry and names its reference."""
    cfg = json.loads((ROOT / c["file"]).read_text())
    assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
    assert cfg["reduced"] == c["reduced"]
    assert (ROOT / "bench" / "reference" / f"{cfg['reference']}.py").is_file()
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
