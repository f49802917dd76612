"""The readers of the program's own marks.

The map from instructions to model scopes is built from the cls and seg
programs compiled on the CPU at the smoke sizes, and by hand from HLO text.
The readers run on traces recorded on a TPU v5e:
`bench/testdata/{cls,seg}_scoped.xplane.pb.gz` are short slices of the
`cls-modelnet-closed` and `seg-s3dis-closed` cells, recorded on one chip by
a traced run and compressed with gzip; beside each, `*.scopes.json` holds
the served program's map (`scopes.op_scopes`) for the instructions the
slice ran. Reading them needs only `jax.profiler`.
"""

import gzip
import json
import pathlib
import re
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from benchlib import cell, scopes, xtrace  # noqa: E402

DATA = ROOT / "bench" / "testdata"
SLICES = ("cls", "seg")
STAGES = {"cls": ("group_ms", "partition_ms"), "seg": ("group_ms", "partition_ms", "knn_ms")}
BATCH = 4
TASKS = {"cls": "pointnet2-cls", "seg": "pointnet2-seg"}


def _expected(task: str) -> list[str]:
    from repro.configs.base import get_config

    cfg = get_config(TASKS[task], smoke=True)
    sa = [f"sa{i}/{s}" for i in range(1, len(cfg.sa) + 1)
          for s in ("partition", "fps", "query", "group", "mlp")]
    if task == "cls":
        return sa + ["global/mlp", "global/pool", "head"]
    fp = [f"fp{i}/{s}" for i in range(1, len(cfg.sa) + 1) for s in ("knn", "interp", "mlp")]
    return sa + fp + ["head"]


@pytest.fixture(scope="module")
def programs():
    """Compiled text and scope map of the cls and seg smoke programs (CPU)."""
    from repro.configs.base import get_config
    from repro.core.accelerator import PC2IMAccelerator

    out = {}
    for task, name in TASKS.items():
        cfg = get_config(name, smoke=True)
        spec = jax.ShapeDtypeStruct((BATCH, cfg.n_points, 3), jnp.float32)
        text = scopes.compiled_text(PC2IMAccelerator(cfg), spec)
        out[task] = (text, scopes.hlo_op_scopes(text))
    return out


@pytest.mark.parametrize(
    "task,scope", [(t, s) for t in TASKS for s in _expected(t)], ids=lambda v: v
)
def test_compiled_program_carries_every_stage_scope(programs, task, scope):
    _, scope_map = programs[task]
    assert any(p == scope or p.startswith(scope + "/") for p in scope_map.values())


@pytest.mark.parametrize("task", TASKS)
def test_op_scopes_maps_every_instruction(programs, task):
    text, scope_map = programs[task]
    names = set(re.findall(r"^\s*(?:ROOT\s+)?%([^\s=]+) = ", text, re.M))
    assert names and set(scope_map) == names


def test_op_scopes_through_the_adapter_at_the_clouds_width():
    """The map is built from the configuration's adapter, lowered at xyz + features."""
    from repro.configs.base import get_config

    cfg = get_config("pointnet2-seg", smoke=True)
    model = {"name": "s", "task": "seg", "n_points": cfg.n_points, "n_classes": cfg.n_classes,
             "in_features": 3, "fp_mlp": list(cfg.fp_mlp), "head": list(cfg.head),
             "sa": [{"n_centroids": s.n_centroids, "radius": s.radius, "nsample": s.nsample,
                     "mlp": list(s.mlp)} for s in cfg.sa],
             "preproc": "pc2im", "aggregation": "delayed", "msp_depth": cfg.msp_depth}
    ctx = SimpleNamespace(program=cell.load_program("pointnet2"), model=model, quant="none",
                          batch=2, width=6)
    scope_map = scopes.op_scopes(ctx)
    assert scope_map is ctx.op_scopes
    assert any(p.startswith("fp2/knn") for p in scope_map.values())
    assert any(p.startswith("sa1/mlp") for p in scope_map.values())


def test_op_scopes_of_a_program_that_hides_its_artifact():
    """A program without `infer_program` (the parent's) gives no map, no error."""
    spec = jax.ShapeDtypeStruct((BATCH, 16, 3), jnp.float32)
    assert scopes.compiled_text(SimpleNamespace(init=None), spec) is None


@pytest.mark.parametrize("op_name,path", [
    ("jit(<lambda>)/sa1/vmap(group)/jit(take_along_axis)/gather", "sa1/group"),
    ("jit(<lambda>)/fp2/knn/vmap()/reduce", "fp2/knn"),
    ("jit(<lambda>)/sa1/query/vmap()/broadcast_in_dim;jit(<lambda>)/sa2/query/x", "sa1/query"),
    ("jit(<lambda>)/sa1/group/jit(take_along_axis)", "sa1/group"),
    ("jit(<lambda>)/reduce_sum", ""),
    ("points", ""),
])
def test_scope_path_of_an_op_name(op_name, path):
    assert scopes.scope_path(op_name) == path


def test_an_instruction_outside_every_scope_takes_its_operands_path_or_none():
    text = "\n".join([
        "%wrapped (p: f32[4]) -> f32[2] {",
        "  ROOT %reduce-window.1 = f32[2] reduce-window(%p, %c), to_apply=%region",
        "}",
        "ENTRY %main (x: f32[4]) -> f32[2] {",
        '  %x = f32[4] parameter(0), metadata={op_name="points"}',
        "  %wrapped-rw = f32[2] fusion(%x), kind=kLoop, calls=%wrapped",
        '  %min.1 = f32[2] minimum(%wrapped-rw, %wrapped-rw), metadata={op_name="jit(f)/sa1/partition/min"}',
        '  ROOT %copy.2 = f32[2] copy(%min.1), metadata={op_name="points"}',
        "}",
    ])
    scope_map = scopes.hlo_op_scopes(text)
    assert scope_map["x"] == "" and scope_map["wrapped-rw"] == ""
    assert scope_map["reduce-window.1"] == ""
    assert scope_map["copy.2"] == "sa1/partition"


def test_an_instruction_without_metadata_takes_its_first_scoped_operands_path():
    text = "\n".join([
        "ENTRY %main (x: f32[8]) -> f32[8] {",
        "  %x = f32[8] parameter(0)",
        '  %fusion.1 = f32[8] fusion(%x), kind=kLoop, calls=%f, metadata={op_name="jit(f)/fp1/knn/sub"}',
        "  %copy.2 = f32[8] copy(%fusion.1)",
        "  ROOT %fusion.3 = f32[8] fusion(%x, %copy.2), kind=kCustom, calls=%g",
        "}",
    ])
    scope_map = scopes.hlo_op_scopes(text)
    assert scope_map["copy.2"] == "fp1/knn" and scope_map["fusion.3"] == "fp1/knn"
    assert scope_map["x"] == ""


@pytest.fixture(scope="module", params=SLICES)
def recorded(request, tmp_path_factory):
    """The context a traced run of the slice's cell hands its readers."""
    name = request.param
    path = tmp_path_factory.mktemp(name) / f"{name}.xplane.pb"
    path.write_bytes(gzip.decompress((DATA / f"{name}_scoped.xplane.pb.gz").read_bytes()))
    trace = xtrace.load(str(path))
    scope_map = json.loads((DATA / f"{name}_scoped.scopes.json").read_text())
    return name, SimpleNamespace(trace=trace, chips=1, op_scopes=scope_map)


def _read(metric, ctx):
    return cell.load_reader(metric)(ctx)


def test_gap_parts_sum_to_the_mean_module_gap(recorded):
    """Results and inputs split each gap: their means sum to dispatch_gap_ms."""
    _, ctx = recorded
    results = _read("gap_results_ms", ctx)
    inputs = _read("gap_inputs_ms", ctx)
    gaps = xtrace.module_gaps_s(ctx.trace)
    assert len(gaps) >= 2 and results > 0 and inputs > 0
    assert results + inputs == pytest.approx(sum(gaps) / len(gaps) * 1e3, abs=1e-9)
    assert results + inputs == pytest.approx(_read("dispatch_gap_ms", ctx), abs=1e-9)


def test_every_gap_is_cut_at_a_completion(recorded):
    """Each gap of the slice holds the end of one batch.complete span."""
    _, ctx = recorded
    ends = [e.end for e in ctx.trace.host if e.name == scopes.COMPLETE]
    for evs in ctx.trace.modules.values():
        for a, b in zip(evs, evs[1:]):
            assert any(a.end <= t <= b.start for t in ends)


@pytest.mark.parametrize("metric", ["group_ms", "partition_ms", "knn_ms"])
def test_stage_readers(recorded, metric):
    """A stage's device ms per program is positive and less than a program."""
    name, ctx = recorded
    value = _read(metric, ctx)
    if metric not in STAGES[name]:
        assert value is None or value == 0.0  # cls has no propagation stage
        return
    programs = [e.dur for d in ctx.trace.chips for e in ctx.trace.modules[d]]
    assert 0 < value < sum(programs) / len(programs) * 1e-6


def test_scope_map_covers_the_traced_op_time(recorded):
    """Nearly all traced op time lies under a model stage."""
    _, ctx = recorded
    total = scoped = 0.0
    for d in ctx.trace.chips:
        for e in ctx.trace.ops[d]:
            total += e.dur
            scoped += e.dur if ctx.op_scopes.get(e.name) else 0.0
    assert scoped / total >= 0.95


def _event(name, start, dur):
    return xtrace.Event(name, float(start), float(dur))


def _synthetic(host):
    modules = {0: [_event("p", 0, 10), _event("p", 20, 10), _event("p", 34, 10)]}
    return xtrace.Trace({0: [_event("op", 0, 10)]}, modules, host)


def test_gap_split_by_hand():
    """Cuts at the first completion after each program, clamped into its gap."""
    host = [_event("batch.h2d", 12, 3), _event("batch.complete", 11, 5),
            _event("batch.complete", 30, 10)]
    ctx = SimpleNamespace(trace=_synthetic(host), chips=1)
    # gap 1: 10 -> 20, cut at 16; gap 2: 30 -> 34, completion ends at 40: clamped to 34
    assert scopes.split_gaps(ctx) == [pytest.approx((6e-9, 4e-9)), pytest.approx((4e-9, 0.0))]
    assert _read("gap_results_ms", ctx) == pytest.approx(5e-6)
    assert _read("gap_inputs_ms", ctx) == pytest.approx(2e-6)


def test_readers_find_nothing_without_the_marks():
    """A program without completion spans or scope map gives None, no error."""
    ctx = SimpleNamespace(trace=_synthetic([_event("XlaLinearize", 12, 3)]), chips=1)
    assert _read("gap_results_ms", ctx) is None and _read("gap_inputs_ms", ctx) is None
    empty = SimpleNamespace(trace=xtrace.Trace({}, {}, []), chips=1)
    assert all(_read(m, empty) is None for m in ("group_ms", "partition_ms", "knn_ms"))
    four = SimpleNamespace(trace=_synthetic([_event("batch.complete", 11, 5)]), chips=4)
    assert _read("gap_results_ms", four) is None  # host spans name no chip
