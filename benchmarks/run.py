"""Benchmark driver — one section per paper table/figure + kernel wall-times.

Prints ``name,us_per_call,derived`` CSV:
  * model-derived rows (fig12a/b/c, fig13, roofline): us_per_call empty,
    derived = model value (with the paper's claim inline);
  * microbenchmark rows: wall-clock us/call of the core ops on this host.
"""

from __future__ import annotations

import pathlib
import sys
import time

import jax
import jax.numpy as jnp

# make `import benchmarks.*` work when invoked as `python benchmarks/run.py`
_ROOT = str(pathlib.Path(__file__).resolve().parent.parent)
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def _timeit(fn, *args, iters: int = 5) -> float:
    fn(*args)  # compile
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def microbench() -> list[dict]:
    from repro.core import fps as F
    from repro.core import partition as P
    from repro.core import query as Q
    from repro.kernels.fps.ops import fps_tiles
    from repro.kernels.sc_matmul.ops import sc_matmul_op
    from repro.data.pointclouds import sample_batch

    pts, _, _ = sample_batch(jax.random.PRNGKey(0), 1, 2048)
    pts = pts[0]
    rows = []
    f_l2 = jax.jit(lambda p: F.fps(p, 512, metric="l2"))
    f_l1 = jax.jit(lambda p: F.fps(p, 512, metric="l1"))
    rows.append({"name": "micro/fps_l2_2048to512", "us": _timeit(f_l2, pts)})
    rows.append({"name": "micro/fps_l1_2048to512", "us": _timeit(f_l1, pts)})
    part = jax.jit(lambda p: P.median_partition(p, 3).tiles)
    rows.append({"name": "micro/msp_partition_2048_d3", "us": _timeit(part, pts)})
    tiles = P.median_partition(pts, 3)
    tiled = jnp.take(pts, tiles.tiles, axis=0)
    tiled_fps = jax.jit(lambda t: fps_tiles(t, 64, backend="xla"))
    rows.append({"name": "micro/tiled_fps_8x256to64", "us": _timeit(tiled_fps, tiled)})
    c = pts[:256]
    bq = jax.jit(lambda p, c: Q.ball_query(p, c, 0.3, 32).idx)
    lq = jax.jit(lambda p, c: Q.lattice_query(p, c, 0.3, 32).idx)
    rows.append({"name": "micro/ball_query_256x2048", "us": _timeit(bq, pts, c)})
    rows.append({"name": "micro/lattice_query_256x2048", "us": _timeit(lq, pts, c)})
    xq = jax.random.randint(jax.random.PRNGKey(1), (256, 512), -32768, 32768, jnp.int32)
    wq = jax.random.randint(jax.random.PRNGKey(2), (512, 256), -32768, 32768, jnp.int32)
    scm = jax.jit(lambda x, w: sc_matmul_op(x, w, backend="xla"))
    ref = jax.jit(lambda x, w: (x.astype(jnp.float32) @ w.astype(jnp.float32)))
    rows.append({"name": "micro/sc_matmul_256x512x256_w16a16", "us": _timeit(scm, xq, wq)})
    rows.append({"name": "micro/f32_matmul_256x512x256", "us": _timeit(ref, xq, wq)})
    return rows


def engine_bench(b: int = 8, n: int = 2048) -> list[dict]:
    """Batched PreprocessEngine vs a per-cloud python loop (same pipeline).

    Rows report us/call; derived = clouds/sec.  The batched engine folds the
    B clouds' MSP tiles into one kernel grid — one dispatch instead of B.
    """
    import functools

    from repro.core.engine import EngineConfig, PreprocessEngine
    from repro.core.preprocess import preprocess_pc2im
    from repro.data.pointclouds import sample_batch

    pts, _, _ = sample_batch(jax.random.PRNGKey(0), b, n)
    engine = PreprocessEngine(
        EngineConfig(pipeline="pc2im", n_centroids=512, radius=0.3, nsample=16, depth=3)
    )
    one = jax.jit(
        functools.partial(preprocess_pc2im, n_centroids=512, radius=0.3, nsample=16, depth=3)
    )

    def batched(x):
        return engine(x).centroid_idx

    def loop(x):
        return [one(x[i]).centroid_idx for i in range(b)]

    rows = []
    us_b = _timeit(batched, pts, iters=10)
    us_l = _timeit(loop, pts, iters=10)
    rows.append({"name": f"engine/pc2im_b{b}_{n}", "us": us_b, "derived": b / (us_b / 1e6)})
    rows.append({"name": f"engine/pc2im_loop{b}_{n}", "us": us_l, "derived": b / (us_l / 1e6)})
    return rows


def accelerator_bench(b: int = 8) -> list[dict]:
    """End-to-end PC2IMAccelerator forward: float vs SC W16A16 feature path.

    One compiled artifact per (config, policy); rows report us/call and
    derived clouds/sec, so the SC-CIM path shows up in the perf trajectory
    next to the preprocessing engine rows.
    """
    from repro.configs.base import get_config
    from repro.core.accelerator import get_accelerator
    from repro.core.policy import ExecutionPolicy
    from repro.data.pointclouds import sample_batch

    cfg = get_config("pointnet2-cls", smoke=True)
    pts, _, _ = sample_batch(jax.random.PRNGKey(0), b, cfg.n_points)
    accel_f = get_accelerator(cfg, ExecutionPolicy(quant="none"))
    accel_q = get_accelerator(cfg, ExecutionPolicy(quant="sc_w16a16"))
    params = accel_f.init(jax.random.PRNGKey(1))

    rows = []
    for tag, accel in (("fp32", accel_f), ("sc_w16a16", accel_q)):
        us = _timeit(lambda p, x, a=accel: a.infer(p, x), params, pts, iters=10)
        rows.append({
            "name": f"accelerator/pc2im_b{b}_{tag}",
            "us": us,
            "derived": b / (us / 1e6),
        })
    return rows


def serve_bench(smoke: bool = False) -> list[dict]:
    """Open-loop load benchmark: ServingRuntime vs naive per-request path
    (see benchmarks/serve_load.py).  Rows: us = p95 latency, derived = note."""
    from benchmarks import serve_load

    return serve_load.run(smoke=smoke)


def serve_cache_bench(smoke: bool = False) -> list[dict]:
    """Cross-request preprocess cache: cached vs uncached runtime on a
    temporally-correlated sweep trace (see benchmarks/serve_load.py).
    ASSERTS hit-rate > 0 on the duplicate trace and bitwise parity of every
    response vs the uncached path — failures raise and fail the lane."""
    from benchmarks import serve_load

    return serve_load.run_cache(smoke=smoke)


def pipeline_bench(smoke: bool = False) -> list[dict]:
    """Preprocess/feature overlap: PipelinedExecutor vs blocking sequential
    infer over one micro-batch stream (see benchmarks/pipeline_overlap.py)."""
    from benchmarks import pipeline_overlap

    return pipeline_overlap.run(smoke=smoke)


def serve_slo_bench(smoke: bool = False) -> list[dict]:
    """SLO control plane under overload + mid-run replica kill (see
    benchmarks/serve_load.run_slo).  ASSERTS the control-plane contracts —
    interactive sheds nothing and holds its p95 budget, bulk absorbs ALL
    shedding, and the autoscaler rejoins the killed replica with >= 90% of
    pre-kill throughput — failures raise and fail the lane."""
    from benchmarks import serve_load

    return serve_load.run_slo(smoke=smoke)


def serve_shard_bench(smoke: bool = False) -> list[dict]:
    """Mesh-sharded replicas vs 1-device replicas on a closed-loop trace
    (see benchmarks/serve_load.run_shard).  Runs in a forced-host-device
    subprocess and ASSERTS bitwise parity of every sharded response against
    the single-device reference — a parity break fails the lane."""
    from benchmarks import serve_load

    return serve_load.run_shard(smoke=smoke)


def serve_adapt_bench(smoke: bool = False) -> list[dict]:
    """Adaptive control plane: feedback-tuned knobs vs static defaults on a
    shifted size-distribution trace offered above the static capacity (see
    benchmarks/serve_load.run_adapt).  ASSERTS the controller applied >= 1
    reconfiguration with logged evidence, every response is bitwise-equal
    to the direct accelerator reference, no request is lost or duplicated
    across the live swap, adapted knobs beat static in throughput or p95,
    and DRR gives the bulk class >= 0.8x its weight share under a
    saturating two-class burst with zero interactive deadline expiries —
    failures raise and fail the lane."""
    from benchmarks import serve_load

    return serve_load.run_adapt(smoke=smoke)


def _print_rows(rows: list) -> None:
    """Print wall-clock rows as name,us,note CSV (one place for the format)."""
    import math

    for row in rows:
        us = "" if math.isnan(row["us"]) else f"{row['us']:.1f}"
        print(f"{row['name']},{us},{row['note']}")


def main() -> None:
    import importlib

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    steps = 0
    smoke = "--smoke" in sys.argv[1:]
    for a in sys.argv[1:]:
        if a.startswith("--train-steps="):
            steps = int(a.split("=")[1])

    print("name,us_per_call,derived")
    if smoke:
        # CI lane: the serving-runtime load benchmark, the correlated-sweep
        # preprocess-cache benchmark (asserting hit-rate > 0 and bitwise
        # parity vs the uncached path), the pipelined-overlap lane, the SLO
        # control-plane lane (two-class overload trace with a mid-run replica
        # kill, asserting shed isolation, the interactive p95 budget and warm
        # rejoin recovery) + the sharded mesh-replica lane (forced-host-device
        # subprocess asserting parity of sharded vs single-device
        # responses) + the adaptive control-plane lane (feedback-tuned knobs
        # vs static defaults, asserting convergence with logged evidence,
        # bitwise parity across the live reconfiguration, the adapted-beats-
        # static contract and the DRR weight-share floor), reduced size —
        # keeps the open-loop path, the cache hot path, the stage-overlap
        # speedup, the control plane, the sharded dispatch path and the
        # adaptation loop exercised on every push without the
        # full paper-table sweep.
        _print_rows(serve_bench(smoke=True))
        _print_rows(serve_cache_bench(smoke=True))
        _print_rows(pipeline_bench(smoke=True))
        _print_rows(serve_slo_bench(smoke=True))
        _print_rows(serve_shard_bench(smoke=True))
        _print_rows(serve_adapt_bench(smoke=True))
        return
    for mod_name, kwargs in [
        ("benchmarks.fig12b_preproc_energy", {}),
        ("benchmarks.fig12c_sccim_fom", {}),
        ("benchmarks.fig13_system", {}),
        ("benchmarks.fig12a_accuracy", {"steps": steps}),
        ("benchmarks.roofline", {}),
    ]:
        mod = importlib.import_module(mod_name)
        for row in mod.run(**kwargs):
            claim = f" (claim: {row['claim']})" if row.get("claim") else ""
            print(f"{row['name']},,{row['value']:.6g}{claim}")
    for row in microbench():
        print(f"{row['name']},{row['us']:.1f},")
    for row in engine_bench():
        print(f"{row['name']},{row['us']:.1f},{row['derived']:.1f} clouds/s")
    for row in accelerator_bench():
        print(f"{row['name']},{row['us']:.1f},{row['derived']:.1f} clouds/s")
    _print_rows(serve_bench())
    _print_rows(serve_cache_bench())
    _print_rows(pipeline_bench())
    _print_rows(serve_slo_bench())
    _print_rows(serve_shard_bench())
    _print_rows(serve_adapt_bench())


if __name__ == "__main__":
    main()
