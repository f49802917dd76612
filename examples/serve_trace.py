"""Request-lifecycle tracing demo: trace a serve run, profile it for Perfetto.

    PYTHONPATH=src python examples/serve_trace.py
    PYTHONPATH=src python examples/serve_trace.py --requests 96 --out my_profile

Runs a traced `ServingRuntime` (TraceConfig attached, periodic Reporter
printing one metrics line per interval) over a small open-loop trace of
mixed-size clouds, then shows every consumer of the trace stream:

  * the per-SLO-class stage breakdown (`stage_breakdown.format_rows()`) —
    p50/p95 of where each request's latency went, queue wait through the
    execute stage, cross-checked so the stages sum to measured e2e;
  * the batch cross-check (`batch_crosscheck`) tying batch-span durations
    back to the `BatchRecord` totals the metrics layer recorded;
  * a JAX profiler trace of the served window (`jax.profiler.trace` with
    `create_perfetto_trace=True`) — open its `perfetto_trace.json.gz` at
    https://ui.perfetto.dev to see the serving stage spans (`batch.h2d`,
    `batch.execute`, `batch.complete`, ...) on the host threads beside the
    device ops, whose names carry the model's stage scopes;
  * the Prometheus text exposition of the final metrics snapshot.
"""

import argparse
import glob
import os
import time

import jax
import numpy as np

from repro.configs.base import get_config
from repro.core.accelerator import get_accelerator
from repro.serve import (
    RuntimeConfig,
    ServingRuntime,
    TraceConfig,
    batch_crosscheck,
    prometheus_text,
    request_timelines,
    stage_breakdown,
    trace_problems,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--rate", type=float, default=150.0,
                    help="open-loop arrival rate, requests/s")
    ap.add_argument("--out", default="pc2im_profile",
                    help="profiler output directory (holds the Perfetto trace)")
    args = ap.parse_args()

    cfg = get_config("pointnet2-cls", smoke=True)  # n_points=256, CPU-friendly
    params = get_accelerator(cfg).init(jax.random.PRNGKey(0))
    rt = ServingRuntime(cfg, params, RuntimeConfig(
        max_batch=4,
        max_wait_s=0.01,
        max_queue=max(64, args.requests),
        trace=TraceConfig(sample=1.0),  # trace every request
        report_interval_s=0.5,          # Reporter prints to stderr
    ))
    print(rt)
    print("warming up (one jit trace per bucket x policy)...")
    rt.warmup()

    rng = np.random.default_rng(0)
    clouds = [rng.standard_normal((n, 3)).astype(np.float32)
              for n in (160, 256, 320)]
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, size=args.requests))
    futs = []
    t0 = time.perf_counter()
    with jax.profiler.trace(args.out, create_perfetto_trace=True), rt:
        for i in range(args.requests):
            time.sleep(max(0.0, t0 + arrivals[i] - time.perf_counter()))
            futs.append(rt.submit(clouds[i % len(clouds)]))
        for f in futs:
            f.result(timeout=300)
    wall = time.perf_counter() - t0

    events = rt.tracer.events()
    problems = trace_problems(events)
    timelines = request_timelines(events)
    print(f"\nserved {args.requests} requests in {wall:.2f}s — "
          f"{len(events)} trace events ({rt.tracer.dropped} dropped), "
          f"{len(timelines)} request spans, "
          f"{len(problems)} malformed")

    print("\nper-class stage breakdown (p50/p95 seconds per stage):")
    for line in stage_breakdown(events).format_rows().splitlines():
        print(" ", line)

    checks = batch_crosscheck(events, rt.metrics.batch_records)
    if checks:
        worst = max(checks, key=lambda c: c.rel_err)
        print(f"\nbatch span vs BatchRecord cross-check: {len(checks)} batches,"
              f" worst rel_err {worst.rel_err:.1%} (batch {worst.batch_id})")

    found = glob.glob(os.path.join(args.out, "plugins", "profile", "*",
                                   "perfetto_trace.json.gz"))
    print(f"\nprofiler trace: {max(found, key=os.path.getmtime) if found else 'none'}"
          f" — load it at https://ui.perfetto.dev")

    print("\nPrometheus exposition of the final snapshot:")
    for line in prometheus_text(rt.metrics.snapshot()).splitlines():
        print(" ", line)


if __name__ == "__main__":
    main()
