"""One run of one cell: set-up, the measured window, the check, the result line.

    python3 bench/run_cell.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration (`bench/configs/<config>.json`) gives the model's
sizes, the execution policy and the comparison's limit, and names the
program adapter `bench/programs/<program>.py`, which turns the model's
sizes into the program's configuration and lists its linears, and the
plain reference `bench/reference/<reference>.py`. Its traffic mix
(`bench/traffic/<mix>.json`) gives the clients, the cloud sizes and the
per-point channels beyond xyz. The runtime keeps every batching knob at the
program's default, with one replica per chip of the cell.

With `--trace 0` the result carries the cell's end-to-end metrics; with
`--trace 1` the JAX profiler records the last `TRACE_SLICE_S` seconds of
the window, and the result carries the cell's per-layer metrics, read by
`bench/metrics/<metric>.py`.

`--control` runs the comparison's control instead (never part of a
benchmark run): the program at the configuration's lower precision, or the
reference at fewer matmul passes in the program's place. Its `correct`
must come out false.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import threading
import time
from types import ModuleType

import numpy as np

from benchlib import correct as correct_mod
from benchlib import traffic, work, xtrace

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
TRACE_SLICE_S = 2.0
EXIT_NO_CHIP = 3


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    """A workload of `BENCHMARK.json` with its configuration and traffic files."""

    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    program: ModuleType


def load_cell(workload: str, root: pathlib.Path = ROOT) -> Cell:
    """Resolve a workload name to its entry, configuration and traffic mix."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    if mix["loop"] != "closed":
        raise ValueError(f"{w['traffic']}: only the closed loop is implemented")

    def mine(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in spec["end_to_end"] if mine(m)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if m["moves"] in moved and mine(m)]
    program = load_program(config["program"], root)
    return Cell(workload, int(w["chips"]), config, mix, e2e, layer, program)


def _load(kind: str, name: str, root: pathlib.Path) -> ModuleType:
    """Import `bench/<kind>/<name>.py` by its path; a missing file is an error naming it."""
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"bench/{kind}/{name}.py not found under {root}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(metric: str, root: pathlib.Path = ROOT):
    """The `read(ctx)` function of `bench/metrics/<metric>.py`."""
    return _load("metrics", metric, root).read


def load_program(name: str, root: pathlib.Path = ROOT) -> ModuleType:
    """The program adapter `bench/programs/<name>.py`: `config(model)`, `linear_shapes(model)`."""
    return _load("programs", name, root)


def load_reference(name: str, root: pathlib.Path = ROOT) -> ModuleType:
    """The plain reference `bench/reference/<name>.py` (see `benchlib.correct`)."""
    return _load("reference", name, root)


@dataclasses.dataclass
class RunContext:
    """What a per-layer reader may read after a traced run.

    `program` is the configuration's program adapter; `width` is the
    served clouds' channels per point, xyz and features.
    """

    model: dict
    program: ModuleType
    quant: str
    batch: int
    width: int
    chips: int
    peaks: dict
    loop: traffic.LoopResult
    trace: xtrace.Trace | None

    @property
    def clouds_per_s(self) -> float:
        """Clouds answered inside the window per second of it."""
        return self.loop.completed_in_window() / (self.loop.end - self.loop.start)


def end_to_end(name: str, loop: traffic.LoopResult, setup_s: float) -> float:
    """One end-to-end metric of a run, on the host clock."""
    if name == "setup_s":
        return setup_s
    if name == "clouds_per_s":
        return loop.completed_in_window() / (loop.end - loop.start)
    raise KeyError(f"no end-to-end metric {name!r}")


def run(workload: str, seed: int, seconds: float, trace: bool, *, control: bool = False,
        t_process: float | None = None, require_chip: bool = True,
        model_override: dict | None = None, policy_override: dict | None = None,
        compile_cache: bool = True, root: pathlib.Path = ROOT) -> dict:
    """Run one cell once and return the result object (see module doc).

    The keywords after `control` serve the tests: a CPU run at smoke sizes,
    another kernel backend, no persistent compilation cache.
    """
    t_process = time.perf_counter() if t_process is None else t_process
    cell = load_cell(workload, root)
    cfg = cell.config
    model = dict(cfg["model"], **(model_override or {}))

    import jax

    devices = jax.devices()
    if require_chip and (jax.default_backend() != "tpu" or len(devices) < cell.chips):
        raise NoChip(
            f"{workload} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devices)} {jax.default_backend()} device(s)"
        )
    devices = devices[: cell.chips]
    sys.path.insert(0, str(root / "src"))
    from repro.core.policy import ExecutionPolicy
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve.runtime import ServingRuntime

    if compile_cache:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if cfg["matmul_precision"] != "default":
        jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])

    quant = cfg["policy"]["quant"]
    served_quant = cfg["control"]["policy_quant"] if control and "policy_quant" in cfg["control"] else quant
    policy = ExecutionPolicy(quant=served_quant, **(policy_override or {}))
    ref = load_reference(cfg["reference"], root)
    params = jax.jit(lambda k: ref.init_params(k, model))(traffic.jax_key(seed))
    rt = ServingRuntime(cell.program.config(model), params, policy=policy, devices=devices)
    rt.warmup(policies=(policy,))
    pool = traffic.make_pool(cell.mix, seed)
    keep = correct_mod.sample_slots([len(c) for c in pool], cfg["compare"]["sample"], seed)
    batch = rt.scheduler.config.max_batch
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    marks: dict[str, float] = {}

    def on_start(t0: float) -> None:
        marks["setup_s"] = t0 - t_process
        if trace:
            delay = max(0.0, seconds - TRACE_SLICE_S)

            def start_profiler():
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0  # it would slow the host it measures
                jax.profiler.start_trace(log_dir, profiler_options=opts)
                marks["trace_t0"] = time.perf_counter()

            timer = threading.Timer(delay, start_profiler)
            timer.daemon = True
            timer.start()
            marks["timer"] = timer

    def on_end() -> None:
        if trace:
            marks["timer"].join()
            marks["trace_window_s"] = time.perf_counter() - marks["trace_t0"]
            jax.profiler.stop_trace()

    gc.collect()
    gc.freeze()  # the window's collections then walk only what it allocates
    rt.start()
    loop = traffic.closed_loop(rt.submit, pool, int(cell.mix["clients"]), seconds, keep,
                               on_start=on_start, on_end=on_end)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)
    rt.stop()
    del rt, params
    gc.unfreeze()
    gc.collect()
    print(f"requests: {loop.attempted} attempted, {loop.failed()} failed, "
          f"{loop.completed_in_window()} answered in the window")
    if loop.errors:
        print(f"first failure: {loop.errors[min(loop.errors)]}")

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": cell.chips,
              "memory_peak_bytes": peak}
    result = {"correct": False, "attempted": loop.attempted, "failed": loop.failed()}
    metrics: dict[str, dict] = {}
    if trace:
        tr = xtrace.load(xtrace.find_xplane(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        device["busy_s"] = xtrace.busy_s(tr)
        device["window_s"] = marks["trace_window_s"]
        peaks = work.peaks_for(dev.device_kind) if require_chip else {}
        ctx = RunContext(model=model, program=cell.program, quant=served_quant, batch=batch,
                         width=pool[0].shape[1], chips=cell.chips, peaks=peaks, loop=loop,
                         trace=tr)
        for m in cell.per_layer:
            value = load_reader(m["name"], root)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": xtrace.top_ops(tr), "idle_gaps": xtrace.idle_gaps(tr)}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": end_to_end(m["name"], loop, marks["setup_s"]),
                                  "unit": m["unit"]}

    comp = cfg["compare"]
    clouds = {s: pool[s] for s in loop.answers}
    refs = correct_mod.reference_answers(ref, model, seed, clouds, quant=quant, passes=6,
                                         block=comp["block"])
    served = loop.answers
    if control and "reference_passes" in cfg["control"]:
        served = correct_mod.reference_answers(
            ref, model, seed, clouds, quant=quant,
            passes=int(cfg["control"]["reference_passes"]), block=comp["block"])
    worst = correct_mod.worst_gap(served, refs)
    checks = {  # JSON has no infinity: a gap with nothing to compare reads null
        "logit_gap": {"value": worst if np.isfinite(worst) else None, "limit": comp["limit"]},
        "unanswered": {"value": loop.failed(), "limit": 0},
        "checked": {"value": len(refs), "limit": 1},
    }
    result["correct"] = bool(worst <= comp["limit"] and loop.failed() == 0 and len(refs) >= 1)
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():
        bound = ">=" if name == "checked" else "<="
        print(f"check {name}: {c['value']} (limit {bound} {c['limit']})", file=sys.stderr)
    return result


def main(argv=None, t_process: float | None = None) -> int:
    """Command-line entry: run the cell, print the result as the last line."""
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the comparison's control; correct must come out false")
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                     control=args.control, t_process=t_process)
    except NoChip as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    print(json.dumps(result))
    return 0
