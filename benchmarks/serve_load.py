"""Open-loop load benchmark: dynamic-batching runtime vs naive per-request serving.

Poisson arrivals (seeded, open-loop: the generator never waits for the
server, so queueing delay is measured honestly) of mixed-size clouds drawn
from data/pointclouds, fired at several arrival rates against

  * naive   — the synchronous per-request path: one worker thread calling
    `make_pointcloud_serve_fns(batch_size=1)["serve_batch"]` per request
    (every request pays a full B=1 artifact call); and
  * runtime — `ServingRuntime` with shape buckets + dynamic micro-batching
    over the same params and compiled-artifact cache.

Rates are calibrated to the measured naive service time on THIS host
(multiples of the naive capacity 1/s_naive), so the comparison is
machine-independent: below capacity both paths keep up and latencies are
comparable; above it the naive path's queue grows without bound while the
batcher amortises the fixed per-call cost over up to `max_batch` clouds.

Rows (printed by benchmarks/run.py as name,us_per_call,derived):
  serve/{path}_r{mult}x : us = p95 latency; derived = throughput + detail.

`run_cache` is the cross-request preprocess-cache benchmark: a
temporally-correlated sweep trace (a pool of static scenes visited
cyclically, duplicate fraction configurable) fired at a cached and an
uncached ServingRuntime.  It ASSERTS hit-rate > 0 on the duplicate trace
and bitwise parity of every response against an uncached direct
recomputation — a failed assertion fails the CI bench-smoke lane.
  serve_cache/{path}_d{dup} : us = p95 latency; derived = throughput + cache detail.

`run_slo` is the SLO control-plane benchmark: a two-class (interactive /
bulk) trace offered ABOVE the pool's measured capacity, with replica 1
chaos-killed mid-run and the autoscaler rejoining it warm.  It ASSERTS the
load-shedding and recovery contracts — the interactive class sheds and
expires nothing and holds its p95 inside the deadline budget, the bulk
class absorbs ALL shedding, and post-rejoin throughput recovers to within
10% of the pre-kill rate — so a regression in the control plane fails the
CI bench-smoke lane, not just a dashboard.
  serve_slo/{class} : us = p95 latency; derived = per-class counts + detail.

`run_adapt` is the adaptive control-plane benchmark: a shifted
size-distribution trace offered above the static runtime's measured
capacity, static knobs vs the AdaptiveController retuning them mid-trace
through the pause-free warm-then-swap path.  It ASSERTS the controller
actuated with logged evidence, bitwise per-request parity vs the direct
accelerator reference across the live swap, zero lost/duplicated
requests, adapted >= static in throughput or p95, and — on a saturating
two-class burst — the DRR weight-share floor for bulk with zero
interactive deadline expiries.
  serve_adapt/{static,adaptive,gain,drr} : us = p95; derived = detail.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CLOUD_SIZES = (160, 256, 320)  # mixed ragged sizes (pad / exact / subsample)
BUCKETS = (192, 256)


def _make_clouds(n_requests: int, width: int, seed: int = 0) -> list[np.ndarray]:
    import jax

    from repro.data.pointclouds import sample_batch

    pts, _, _ = sample_batch(jax.random.PRNGKey(seed), n_requests, max(CLOUD_SIZES))
    pts = np.asarray(pts, np.float32)
    if width > 3:
        pts = np.concatenate(
            [pts, np.zeros((*pts.shape[:2], width - 3), np.float32)], axis=-1
        )
    return [pts[i, : CLOUD_SIZES[i % len(CLOUD_SIZES)]] for i in range(n_requests)]


def _open_loop(submit_fn, clouds, arrivals_s):
    """Fire clouds at their arrival instants; returns (latencies, n_rejected,
    wall_s).  Latency = completion - arrival (queueing included), recorded in
    each future's done-callback so slow waiters don't distort it."""
    lock = threading.Lock()
    latencies: list[float] = []
    rejected = 0
    pending = []
    t0 = time.perf_counter()
    for cloud, at in zip(clouds, arrivals_s):
        wait = (t0 + at) - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        t_arr = time.perf_counter()

        def _record(fut, t_arr=t_arr):
            if fut.exception() is None:
                with lock:
                    latencies.append(time.perf_counter() - t_arr)

        try:
            fut = submit_fn(cloud)
        except Exception:  # noqa: BLE001 — admission backpressure (QueueFull)
            rejected += 1
            continue
        fut.add_done_callback(_record)
        pending.append(fut)
    for fut in pending:
        try:
            fut.result(timeout=600)
        except Exception:  # noqa: BLE001 — failed requests drop out of latency
            pass
    return latencies, rejected, time.perf_counter() - t0


def run(smoke: bool = False, seed: int = 0) -> list[dict]:
    import jax

    from repro.configs.base import get_config
    from repro.core.accelerator import get_accelerator
    from repro.serve import (
        PointCloudServeConfig,
        RuntimeConfig,
        ServingRuntime,
        make_pointcloud_serve_fns,
    )

    cfg = get_config("pointnet2-cls", smoke=True)
    width = 3 + cfg.in_features
    accel = get_accelerator(cfg)
    params = accel.init(jax.random.PRNGKey(seed))

    n_requests = 40 if smoke else 96
    rate_mults = (3.0,) if smoke else (0.8, 2.0, 4.0)
    clouds = _make_clouds(n_requests, width, seed)

    # naive per-request path (B=1 artifact), one worker thread
    naive = make_pointcloud_serve_fns(cfg, PointCloudServeConfig(batch_size=1))

    def naive_one(cloud):
        return naive["serve_batch"](params, [cloud])[0]

    naive_one(clouds[0])  # warm the B=1 artifact
    t = time.perf_counter()
    for c in clouds[:4]:
        naive_one(c)
    s_naive = (time.perf_counter() - t) / 4  # measured service time -> capacity

    # max_batch=4: the occupancy/latency sweet spot on small hosts — B=4
    # roughly halves the per-cloud cost vs B=1 while a partial flush stays
    # cheap; max_wait ~ a few service times bounds the added latency.
    rt_cfg = RuntimeConfig(
        max_batch=4,
        max_wait_s=min(0.02, 4 * s_naive),
        max_queue=max(64, n_requests),
        buckets=BUCKETS,
    )
    rows = []
    for mult in rate_mults:
        rate = mult / s_naive
        rng = np.random.default_rng(seed + int(mult * 10))
        arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_requests))

        with ThreadPoolExecutor(max_workers=1) as ex:
            lat_n, rej_n, wall_n = _open_loop(
                lambda c: ex.submit(naive_one, c), clouds, arrivals
            )
        runtime = ServingRuntime(cfg, params, rt_cfg)
        runtime.warmup()
        with runtime:
            lat_r, rej_r, wall_r = _open_loop(runtime.submit, clouds, arrivals)
        snap = runtime.metrics.snapshot()

        for tag, lat, rej, wall, extra in (
            ("naive", lat_n, rej_n, wall_n, ""),
            ("runtime", lat_r, rej_r, wall_r, f" occ={snap.mean_occupancy:.2f}"),
        ):
            thr = len(lat) / wall if wall > 0 else 0.0
            p95 = float(np.percentile(lat, 95)) if lat else float("nan")
            rows.append({
                "name": f"serve/{tag}_r{mult:g}x",
                "us": p95 * 1e6,
                "note": (
                    f"{thr:.1f} req/s (rate {rate:.1f}/s; p95 {p95 * 1e3:.1f}ms;"
                    f" rej {rej}){extra}"
                ),
            })
        thr_n = len(lat_n) / wall_n if wall_n else 0.0
        thr_r = len(lat_r) / wall_r if wall_r else 0.0
        rows.append({
            "name": f"serve/speedup_r{mult:g}x",
            "us": float("nan"),
            "note": f"runtime/naive throughput {thr_r / thr_n:.2f}x" if thr_n else "n/a",
        })
    return rows


def _sweep_trace(n_requests: int, dup_frac: float, n_points: int, width: int, seed: int):
    """Temporally-correlated sweep trace over a pool of static scenes.

    `n_unique = n_requests * (1 - dup_frac)` distinct scenes are visited
    cyclically — the multi-camera static-rig pattern where every pass after
    the first re-observes scenes already served.  Scenes are snapped to the
    content-hash lattice, so repeats are exact duplicates and EVERY response
    (hit or miss) must be bitwise-equal to the scene's uncached
    recomputation; sub-step sensor jitter keying identically is pinned by
    tests/test_hashing.py.  Returns (scenes, visit order).
    """
    import jax

    from repro.data.pointclouds import sample_batch
    from repro.serve.hashing import DEFAULT_QUANT_STEP

    n_unique = max(1, int(round(n_requests * (1.0 - dup_frac))))
    pts, _, _ = sample_batch(jax.random.PRNGKey(seed), n_unique, n_points)
    pts = np.asarray(pts, np.float64)
    if width > 3:
        pts = np.concatenate(
            [pts, np.zeros((*pts.shape[:2], width - 3), np.float64)], axis=-1
        )
    step = DEFAULT_QUANT_STEP
    scenes = [
        (np.round(pts[i] / step) * step).astype(np.float32) for i in range(n_unique)
    ]
    return scenes, [i % n_unique for i in range(n_requests)]


class _IndexedSubmit:
    """submit_fn wrapper keeping (trace index, future) pairs for parity checks."""

    def __init__(self, runtime):
        self.runtime = runtime
        self.i = -1
        self.futs: list[tuple] = []

    def __call__(self, cloud):
        self.i += 1  # counts every attempt, so indices survive rejections
        fut = self.runtime.submit(cloud)
        self.futs.append((self.i, fut))
        return fut


def run_cache(smoke: bool = False, seed: int = 0) -> list[dict]:
    """Preprocess-cache benchmark: cached vs uncached runtime on sweep traces.

    The >= 50%-duplicate trace is where the cache earns its place (all-hit
    micro-batches skip the preprocess stage outright); the 0%-duplicate
    trace checks the cache-aware path costs nothing measurable when nothing
    repeats.  Raises RuntimeError when the duplicate trace records no hits
    or any response differs bitwise from its scene's uncached recomputation.

    Each (trace, runtime) pair is measured best-of-N: a 48-request open loop
    on a shared host has large run-to-run noise (one descheduled batch moves
    throughput ~20%), and the best rep is the closest observation of what
    each configuration can actually sustain.  Correctness (bitwise parity,
    hits recorded) is asserted on EVERY rep, not just the reported one.
    """
    import jax

    from repro.configs.base import get_config
    from repro.core.accelerator import get_accelerator
    from repro.serve import RuntimeConfig, ServingRuntime

    cfg = get_config("pointnet2-cls", smoke=True)
    width = 3 + cfg.in_features
    n_points = cfg.n_points
    accel = get_accelerator(cfg)
    params = accel.init(jax.random.PRNGKey(seed))

    n_requests = 64 if smoke else 120
    dup_fracs = (0.6, 0.0) if smoke else (0.75, 0.5, 0.0)
    max_batch = 4

    # calibrate the arrival rate to THIS host's uncached capacity: per-request
    # service time at B=max_batch through the fused artifact (min of 5 — the
    # floor is far more stable run-to-run than a small-sample mean, and the
    # rate must not swing with scheduler noise)
    warm = np.zeros((max_batch, n_points, width), np.float32)
    jax.block_until_ready(accel.infer(params, warm))
    times = []
    for _ in range(5):
        t = time.perf_counter()
        jax.block_until_ready(accel.infer(params, warm))
        times.append(time.perf_counter() - t)
    s_req = min(times) / max_batch
    rate = 1.5 / s_req  # above uncached capacity: backlog unless work shrinks

    n_reps = 5
    rows = []
    for dup in dup_fracs:
        scenes, order = _sweep_trace(n_requests, dup, n_points, width, seed)
        trace = [scenes[s] for s in order]
        # rep k of BOTH configurations replays the same arrival schedule, so
        # each rep is a paired comparison under identical offered load
        arrivals_by_rep = [
            np.cumsum(
                np.random.default_rng(seed + int(dup * 100) + 7919 * r)
                .exponential(1.0 / rate, size=n_requests)
            )
            for r in range(n_reps)
        ]

        # uncached direct reference, one per scene (bitwise target for BOTH
        # paths: scenes are lattice-snapped so hits serve the same bytes)
        refs = []
        for scene in scenes:
            batch = np.zeros((max_batch, n_points, width), np.float32)
            batch[0] = scene
            refs.append(np.asarray(accel.infer(params, batch))[0])

        # reps INTERLEAVE the two configurations (uncached then cached within
        # each rep) so host drift — turbo decay, noisy neighbors — lands on
        # both sides of every pair instead of on whichever ran second
        best = {}  # tag -> (thr, p95, rej, snap, stats) of the best-thr rep
        best_p95 = {}
        for arrivals in arrivals_by_rep:
            for tag, cache_bytes in (("uncached", 0), ("cached", 64 * 2**20)):
                rt = ServingRuntime(cfg, params, RuntimeConfig(
                    max_batch=max_batch,
                    max_wait_s=min(0.02, 4 * s_req * max_batch),
                    max_queue=max(64, n_requests),
                    buckets=(n_points,),
                    cache_max_bytes=cache_bytes,
                ))
                rt.warmup()
                submit = _IndexedSubmit(rt)
                with rt:
                    lat, rej, wall = _open_loop(submit, trace, arrivals)
                snap = rt.metrics.snapshot()
                stats = rt.cache_stats()

                mismatches = 0
                for i, fut in submit.futs:
                    if fut.exception() is not None:
                        continue
                    if not np.array_equal(fut.result(), refs[order[i]]):
                        mismatches += 1
                if mismatches:
                    raise RuntimeError(
                        f"serve_cache d{dup:g} {tag}: {mismatches} responses "
                        "differ bitwise from uncached recomputation"
                    )
                if tag == "cached" and dup > 0 and (stats is None or stats.hits == 0):
                    raise RuntimeError(
                        f"serve_cache d{dup:g}: duplicate trace recorded no "
                        f"cache hits ({stats})"
                    )

                thr = len(lat) / wall if wall > 0 else 0.0
                p95 = float(np.percentile(lat, 95)) if lat else float("nan")
                best_p95[tag] = min(best_p95.get(tag, float("inf")), p95)
                if tag not in best or thr > best[tag][0]:
                    best[tag] = (thr, p95, rej, snap, stats)

        results = {}
        for tag in ("uncached", "cached"):
            thr, _, rej, snap, stats = best[tag]
            p95 = best_p95[tag]
            results[tag] = (thr, p95)

            extra = ""
            if tag == "cached":
                extra = (
                    f" hit={snap.cache_hit_rate:.2f} skip={snap.preprocess_skipped}"
                    f" saved={snap.cache_saved_s * 1e3:.0f}ms"
                    f" resident={stats.bytes // 1024}KiB"
                )
            rows.append({
                "name": f"serve_cache/{tag}_d{int(dup * 100)}",
                "us": p95 * 1e6,
                "note": (
                    f"{thr:.1f} req/s best-of-{n_reps} (rate {rate:.1f}/s;"
                    f" p95 {p95 * 1e3:.1f}ms; rej {rej}){extra}"
                ),
            })

        (thr_u, p95_u), (thr_c, p95_c) = results["uncached"], results["cached"]
        rows.append({
            "name": f"serve_cache/speedup_d{int(dup * 100)}",
            "us": float("nan"),
            "note": (
                f"cached/uncached throughput {thr_c / thr_u:.2f}x, "
                f"p95 {p95_u / p95_c:.2f}x lower" if thr_u and p95_c else "n/a"
            ),
        })
    return rows


def _slo_attempt(cfg, params, s_req, *, n_requests, rate, high, low, seed):
    """One serve_slo trace: overload + mid-run kill; returns measurements.

    Drives a 2-replica runtime with shedding and the autoscaler attached,
    kills replica 1 at its `at_batch`-th real batch via the chaos injector,
    and records per-completion (class, arrival, done) stamps on
    time.monotonic() — the same clock the chaos/autoscaler events use, so
    the pre-kill and post-rejoin throughput windows line up exactly.
    """
    from repro.serve import (
        AutoscalerConfig,
        ChaosInjector,
        Fault,
        RuntimeConfig,
        ServingRuntime,
        Shed,
    )

    max_batch = 4
    s_batch = s_req * max_batch
    rt = ServingRuntime(cfg, params, RuntimeConfig(
        max_batch=max_batch,
        max_wait_s=min(0.02, 2 * s_batch),
        max_queue=max(48, n_requests // 4),
        buckets=(cfg.n_points,),
        n_replicas=2,
        shed_threshold=max(24, n_requests // 8),
        # rejoin-only autoscaler: depth thresholds out of reach, so the only
        # actions are fault rejoins — the axis this benchmark measures
        autoscaler=AutoscalerConfig(
            poll_interval_s=0.02,
            rejoin_delay_s=0.15,
            scale_up_depth=1e9,
            scale_down_depth=0.0,
            scale_down_ticks=10**9,
            cooldown_s=600.0,
        ),
    ))
    rt.warmup()
    # kill replica 1 roughly a third into its share of the trace: late
    # enough for a stable pre-kill window, early enough that the post-rejoin
    # window still sees plenty of traffic
    at_batch = max(2, n_requests // (max_batch * 2 * 3))
    chaos = ChaosInjector([Fault(replica_id=1, at_batch=at_batch, kind="kill")])
    chaos.attach(rt.pool)

    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_requests))
    width = 3 + cfg.in_features
    cloud = np.zeros((cfg.n_points, width), np.float32)
    clouds = [
        (cloud + rng.standard_normal(cloud.shape).astype(np.float32))
        for _ in range(8)
    ]

    lock = threading.Lock()
    done = []  # (slo_name, t_arrival, t_done) of successful completions
    shed_by = {high.name: 0, low.name: 0}
    pending = []
    t0 = time.monotonic()
    with rt:
        for i in range(n_requests):
            wait = (t0 + arrivals[i]) - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            slo = high if i % 3 == 0 else low
            t_arr = time.monotonic()

            def _record(fut, name=slo.name, t_arr=t_arr):
                if fut.exception() is None:
                    with lock:
                        done.append((name, t_arr, time.monotonic()))

            try:
                fut = rt.submit(clouds[i % len(clouds)], slo=slo)
            except Shed:
                shed_by[slo.name] += 1
                continue
            except Exception:  # noqa: BLE001 — queue-full backpressure
                continue
            fut.add_done_callback(_record)
            pending.append(fut)
        for fut in pending:
            try:
                fut.result(timeout=600)
            except Exception:  # noqa: BLE001 — shed/expired futures
                pass
        # hold the runtime open until the rejoin lands (bounded)
        deadline = time.monotonic() + 30
        while rt.metrics.rejoins < 1 and time.monotonic() < deadline:
            time.sleep(0.02)
    snap = rt.metrics.snapshot()
    kills = chaos.fired("kill")
    rejoins = [e for e in rt.autoscaler.events if e.action == "rejoin"]
    return {
        "snap": snap,
        "done": done,
        "shed_by": shed_by,
        "t_kill": kills[0].t if kills else None,
        "t_rejoin": rejoins[0].t if rejoins else None,
        "s_batch": s_batch,
    }


def _window_rate(done, t_lo, t_hi):
    """Completions/s inside [t_lo, t_hi]; (rate, count)."""
    n = sum(1 for _, _, t in done if t_lo <= t <= t_hi)
    span = t_hi - t_lo
    return (n / span if span > 0 else 0.0), n


def _probe_capacity(cfg, params, s_req, *, n_probe=48):
    """Closed-loop capacity probe: completions/s through a real 2-replica runtime.

    The overload trace must be calibrated against what the serving stack can
    actually sustain, not against n_replicas / s_infer: on a host where both
    replicas share one core (CI runners), two replicas do NOT double
    throughput, and an analytic rate would overload even the non-sheddable
    interactive share — the p95 assertion would then measure the host, not
    the control plane.  A closed-loop burst (submit everything, wait for
    completion) through the same runtime shape as the trace measures the
    true end-to-end rate, batching and scheduler overhead included.
    """
    from repro.serve import RuntimeConfig, ServingRuntime

    max_batch = 4
    s_batch = s_req * max_batch
    rt = ServingRuntime(cfg, params, RuntimeConfig(
        max_batch=max_batch,
        max_wait_s=min(0.02, 2 * s_batch),
        max_queue=2 * n_probe,
        buckets=(cfg.n_points,),
        n_replicas=2,
    ))
    rt.warmup()
    rng = np.random.default_rng(7)
    width = 3 + cfg.in_features
    clouds = [
        rng.standard_normal((cfg.n_points, width)).astype(np.float32)
        for _ in range(4)
    ]
    with rt:
        t0 = time.perf_counter()
        futs = [rt.submit(clouds[i % len(clouds)]) for i in range(n_probe)]
        for f in futs:
            f.result(timeout=600)
        wall = time.perf_counter() - t0
    return n_probe / wall


def run_slo(smoke: bool = False, seed: int = 0) -> list[dict]:
    """SLO control-plane benchmark: two-class overload + mid-run replica kill.

    One third of the trace is a non-sheddable interactive class with a
    deadline, the rest a sheddable bulk class, offered at 1.5x the measured
    2-replica capacity so the runtime MUST shed.  Replica 1 is killed
    mid-trace; the autoscaler rejoins it warm.  Self-asserting (raises
    RuntimeError, failing CI) on the control-plane contracts:

      * interactive: shed == 0, expired == 0, p95 <= the deadline budget;
      * bulk absorbs ALL shedding (and some shedding happened);
      * exactly one kill, at least one warm rejoin, and post-rejoin
        throughput >= 90% of the pre-kill rate.

    The throughput-recovery check compares completion rates in the
    [start, kill] and [rejoin + margin, end] windows on one shared host —
    an open loop this short is noisy, so the trace is retried up to 3 times
    and only a run that fails on its last attempt raises.  The class
    contracts (shed/expired/parity of counts) are asserted on EVERY
    attempt — they are deterministic and never excused by noise.
    """
    import jax

    from repro.configs.base import get_config
    from repro.core.accelerator import get_accelerator
    from repro.serve import SLOClass

    cfg = get_config("pointnet2-cls", smoke=True)
    width = 3 + cfg.in_features
    n_points = cfg.n_points
    accel = get_accelerator(cfg)
    params = accel.init(jax.random.PRNGKey(seed))

    max_batch = 4
    warm = np.zeros((max_batch, n_points, width), np.float32)
    jax.block_until_ready(accel.infer(params, warm))
    times = []
    for _ in range(5):
        t = time.perf_counter()
        jax.block_until_ready(accel.infer(params, warm))
        times.append(time.perf_counter() - t)
    s_req = min(times) / max_batch
    # 1.5x the MEASURED closed-loop capacity: sustained overload, so shedding
    # is guaranteed, while the interactive third (0.5x capacity) stays
    # servable — _probe_capacity explains why the rate cannot be derived
    # analytically from s_req and the replica count
    capacity = _probe_capacity(cfg, params, s_req)
    rate = 1.5 * capacity
    trace_s = 2.5 if smoke else 5.0
    n_requests = int(min(600 if smoke else 1200, max(96, rate * trace_s)))

    # deadline budget: generous on absolute terms AND in measured batch
    # units, so a slow host doesn't fail on calibration noise; the assertion
    # is against the p95 budget, the class deadline is 2x that (expired==0
    # is strict)
    s_eff = max_batch / capacity  # end-to-end batch time under serving
    p95_budget = max(0.3, 25 * s_eff)
    high = SLOClass(
        "interactive", priority=10, deadline_s=2 * p95_budget,
        sheddable=False, max_wait_s=min(0.005, s_eff),
    )
    low = SLOClass("bulk", priority=-10, deadline_s=None, sheddable=True)

    last_err = None
    for attempt in range(3):
        m = _slo_attempt(
            cfg, params, s_req,
            n_requests=n_requests, rate=rate, high=high, low=low,
            seed=seed + 101 * attempt,
        )
        snap, done = m["snap"], m["done"]
        hi_cls = snap.for_class(high.name)
        lo_cls = snap.for_class(low.name)
        lat_hi = [t1 - t_arr for name, t_arr, t1 in done if name == high.name]
        lat_lo = [t1 - t_arr for name, t_arr, t1 in done if name == low.name]
        p95_hi = float(np.percentile(lat_hi, 95)) if lat_hi else float("nan")
        p95_lo = float(np.percentile(lat_lo, 95)) if lat_lo else float("nan")

        # deterministic class contracts: asserted on every attempt
        if hi_cls is None or hi_cls.shed != 0 or hi_cls.expired != 0:
            raise RuntimeError(
                f"serve_slo: interactive class was shed/expired ({hi_cls})"
            )
        if snap.shed == 0 or lo_cls is None or lo_cls.shed != snap.shed:
            raise RuntimeError(
                "serve_slo: bulk did not absorb all shedding "
                f"(total {snap.shed}, bulk {lo_cls and lo_cls.shed})"
            )
        if snap.evictions < 1:
            raise RuntimeError("serve_slo: chaos kill did not evict")

        # noise-prone contracts: retried
        try:
            if not np.isfinite(p95_hi) or p95_hi > p95_budget:
                raise RuntimeError(
                    f"serve_slo: interactive p95 {p95_hi * 1e3:.1f}ms over "
                    f"budget {p95_budget * 1e3:.1f}ms"
                )
            if m["t_kill"] is None or m["t_rejoin"] is None or snap.rejoins < 1:
                raise RuntimeError(
                    f"serve_slo: kill/rejoin cycle incomplete "
                    f"(kill={m['t_kill']}, rejoin={m['t_rejoin']})"
                )
            t_first = min(t_arr for _, t_arr, _ in done)
            t_last = max(t1 for _, _, t1 in done)
            thr_pre, n_pre = _window_rate(done, t_first, m["t_kill"])
            thr_post, n_post = _window_rate(
                done, m["t_rejoin"] + 2 * m["s_batch"], t_last
            )
            if n_pre < 8 or n_post < 8:
                raise RuntimeError(
                    f"serve_slo: windows too thin (pre {n_pre}, post {n_post})"
                )
            if thr_post < 0.9 * thr_pre:
                raise RuntimeError(
                    f"serve_slo: post-rejoin throughput {thr_post:.1f}/s < 90% "
                    f"of pre-kill {thr_pre:.1f}/s"
                )
        except RuntimeError as e:
            last_err = e
            continue

        recovery_ms = (m["t_rejoin"] - m["t_kill"]) * 1e3
        return [
            {
                "name": "serve_slo/interactive",
                "us": p95_hi * 1e6,
                "note": (
                    f"completed={hi_cls.completed} shed=0 expired=0 "
                    f"p95 {p95_hi * 1e3:.1f}ms <= budget {p95_budget * 1e3:.0f}ms"
                ),
            },
            {
                "name": "serve_slo/bulk",
                "us": p95_lo * 1e6,
                "note": (
                    f"completed={lo_cls.completed} shed={lo_cls.shed} "
                    f"(absorbed 100% of shedding; rate {rate:.1f}/s = 1.5x cap)"
                ),
            },
            {
                "name": "serve_slo/recovery",
                "us": float("nan"),
                "note": (
                    f"kill->rejoin {recovery_ms:.0f}ms; thr pre {thr_pre:.1f}/s"
                    f" post {thr_post:.1f}/s ({thr_post / thr_pre:.2f}x);"
                    f" attempt {attempt + 1}/3"
                ),
            },
        ]
    raise RuntimeError(f"serve_slo: failed after 3 attempts: {last_err}")


# -- sharded mesh-replica lane ------------------------------------------------

# Child script for `run_shard`: runs under 4 FORCED host devices, which must
# be configured via XLA_FLAGS before jax initialises its backend — hence a
# subprocess, mirroring the tests/_multidev.py isolation rule.  Serves the
# same closed-loop trace through 1-device replicas (unsharded baseline) and
# 2-device mesh replicas in both sharding modes, self-asserting every
# response is bitwise-equal to the single-device reference before reporting
# any number (fp32 forward is batch-size independent bitwise, so B=1
# references are exact).  The CPU backend sums a dot in an order that
# depends on its shape, so tensor mode's reference multiplies every linear in
# the same column blocks its devices do, on a micro-batch of the runtime's
# static shape (max_batch rows, filler rows zero).  Rows come back as JSON
# via PC2IM_SHARD_OUT.
_SHARD_CHILD = """\
import json, os, time

import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config
from repro.core.accelerator import PC2IMAccelerator, get_accelerator
from repro.core.policy import ExecutionPolicy
from repro.models import nn
from repro.serve import RuntimeConfig, ServingRuntime

smoke = bool(int(os.environ["PC2IM_SHARD_SMOKE"]))
seed = int(os.environ["PC2IM_SHARD_SEED"])
n_requests = 24 if smoke else 64

cfg = get_config("pointnet2-cls", smoke=True)
width = 3 + cfg.in_features
base = get_accelerator(cfg)
params = base.init(jax.random.PRNGKey(seed))
rng = np.random.default_rng(seed)
clouds = [
    rng.standard_normal((cfg.n_points, width)).astype(np.float32)
    for _ in range(n_requests)
]
refs = [np.asarray(base.infer(params, c[None]))[0] for c in clouds]


def column_blocked_refs(group, max_batch):
    def blocked(p, x, policy=None):
        w = p["w"]
        n = w.shape[1]
        cols = -(-n // group)
        wp = jnp.pad(w, ((0, 0), (0, cols * group - n)))
        y = jnp.concatenate(
            [x @ wp[:, i * cols:(i + 1) * cols] for i in range(group)], axis=-1
        )[..., :n]
        return y + p["b"] if "b" in p else y

    plain, nn.linear = nn.linear, blocked
    try:
        accel = PC2IMAccelerator(cfg)
        out = []
        for c in clouds:
            batch = np.zeros((max_batch,) + c.shape, np.float32)
            batch[0] = c
            out.append(np.asarray(accel.infer(params, batch))[0])
        return out
    finally:
        nn.linear = plain


rows = []
for mode in (None, "batch", "tensor"):
    pol = ExecutionPolicy(sharding=mode)
    per = 1 if mode is None else 2
    mode_refs = column_blocked_refs(per, max_batch=4) if mode == "tensor" else refs
    rt = ServingRuntime(
        cfg,
        params,
        RuntimeConfig(
            max_batch=4, devices_per_replica=per, max_queue=max(64, n_requests)
        ),
        policy=pol,
    )
    rt.warmup((pol,))
    lats, outs = [], []
    t0 = time.perf_counter()
    with rt:
        futs = [(time.perf_counter(), rt.submit(c)) for c in clouds]
        for t_sub, f in futs:
            outs.append(f.result(timeout=600))
            lats.append(time.perf_counter() - t_sub)
    wall = time.perf_counter() - t0
    for o, r in zip(outs, mode_refs):
        assert np.array_equal(o, r), (
            f"serve_shard: sharding={mode} response != single-device bits"
        )
    n_rep = len(rt.pool.replicas)
    tag = mode or "unsharded"
    rows.append({
        "name": f"serve_shard/{tag}",
        "us": float(np.percentile(lats, 95)) * 1e6,
        "note": (
            f"{len(outs) / wall:.1f} req/s over {n_rep}x{per}-device replicas"
            f" (forced host devices); parity bitwise-ok"
        ),
    })

with open(os.environ["PC2IM_SHARD_OUT"], "w") as f:
    json.dump(rows, f)
"""


def run_shard(smoke: bool = False, seed: int = 0) -> list[dict]:
    """Mesh-sharded replica lane: 2-device replicas vs 1-device replicas.

    Runs in a subprocess with ``xla_force_host_platform_device_count=4``
    (the parent process must keep its single-device view) and SELF-ASSERTS
    bitwise parity of every sharded response against the single-device
    reference before any throughput number is reported — a parity break
    fails the lane, not just a dashboard.

    Forced host devices timeshare one CPU, so the throughput columns here
    measure dispatch/overhead plumbing, not real multi-chip scaling.
      serve_shard/{mode} : us = p95 latency; derived = throughput + parity.
    """
    import json
    import os
    import subprocess
    import sys
    import tempfile

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with tempfile.TemporaryDirectory() as td:
        out = os.path.join(td, "rows.json")
        env = dict(os.environ)
        # CPU-only by design: forced host devices exist only on the CPU
        # backend, and this parent may hold the accelerator.  Single-threaded
        # Eigen keeps a row's matmul bits independent of the batch size (see
        # tests/_multidev.py).
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=4"
            " --xla_cpu_multi_thread_eigen=false"
        )
        env["PYTHONPATH"] = "src"
        env["PC2IM_SHARD_OUT"] = out
        env["PC2IM_SHARD_SMOKE"] = str(int(smoke))
        env["PC2IM_SHARD_SEED"] = str(seed)
        res = subprocess.run(
            [sys.executable, "-c", _SHARD_CHILD],
            capture_output=True,
            text=True,
            timeout=1800,
            env=env,
            cwd=repo_root,
        )
        if res.returncode != 0:
            raise RuntimeError(
                f"serve_shard child failed (rc={res.returncode})\n"
                f"--- stdout tail ---\n{res.stdout[-2000:]}\n"
                f"--- stderr tail ---\n{res.stderr[-4000:]}"
            )
        with open(out) as f:
            return json.load(f)


# -- adaptive control-plane lane ----------------------------------------------


def _adapt_scene_pool(width: int, seed: int):
    """Shifted size distribution: clouds clustered well below the static
    256 bucket, so a static runtime pays heavy padding on every batch while
    the controller can re-bucket to the observed sizes.  A small pool of
    distinct scenes (4 per size) keeps the bitwise parity check cheap:
    references are computed per (scene, candidate bucket), not per request.
    """
    rng = np.random.default_rng(seed)
    sizes = (96, 128, 160)
    scenes = [
        rng.standard_normal((n, width)).astype(np.float32)
        for n in sizes
        for _ in range(4)
    ]
    return scenes


def _adapt_attempt(cfg, params, accel, scenes, order, arrivals, ad_cfg):
    """One paired static-vs-adaptive run; returns per-path measurements."""
    from repro.serve import RuntimeConfig, ServingRuntime

    trace = [scenes[s] for s in order]
    out = {}
    for tag in ("static", "adaptive"):
        rt = ServingRuntime(cfg, params, RuntimeConfig(
            max_batch=2,  # the deliberately conservative static default
            max_wait_s=0.005,
            max_queue=len(trace) + 64,
            buckets=(cfg.n_points,),
            adaptive=ad_cfg if tag == "adaptive" else None,
        ))
        rt.warmup()
        submit = _IndexedSubmit(rt)
        with rt:
            lat, rej, wall = _open_loop(submit, trace, arrivals)
        snap = rt.metrics.snapshot()

        # -- deterministic contracts, asserted on every attempt -----------
        # (1) no request lost or duplicated across any swap: every submit
        # produced a future that resolved exactly once, and the books match
        n_ok = sum(1 for _, f in submit.futs if f.exception() is None)
        n_err = sum(1 for _, f in submit.futs if f.exception() is not None)
        assert all(f.done() for _, f in submit.futs)
        if n_ok != len(lat) or n_ok + n_err + rej != len(trace):
            raise RuntimeError(
                f"serve_adapt {tag}: accounting broke — {n_ok} ok + {n_err} "
                f"failed + {rej} rejected != {len(trace)} offered "
                f"({len(lat)} latencies)"
            )
        if snap.completed != n_ok:
            raise RuntimeError(
                f"serve_adapt {tag}: metrics completed {snap.completed} != "
                f"{n_ok} resolved futures (lost or double-counted requests)"
            )

        # (2) bitwise parity: a mid-swap request may have been bucketed
        # under ANY bucket set that was ever active, so its response must
        # equal the direct accelerator reference at one candidate bucket
        from repro.serve import bucket_for, pad_cloud

        decisions = (
            rt.controller.decisions.all() if rt.controller is not None else ()
        )
        bucket_sets = [(cfg.n_points,)] + [
            tuple(d.value) for d in decisions if d.kind == "buckets" and d.applied
        ]
        ref_cache = {}

        def _ref(scene_id, bucket):
            key = (scene_id, bucket)
            if key not in ref_cache:
                scene = scenes[scene_id]
                batch = np.zeros((4, bucket, scene.shape[1]), np.float32)
                batch[0] = pad_cloud(scene, bucket)[0]
                ref_cache[key] = np.asarray(accel.infer(params, batch))[0]
            return ref_cache[key]

        for i, fut in submit.futs:
            if fut.exception() is not None:
                continue
            sid = order[i]
            n = scenes[sid].shape[0]
            candidates = {bucket_for(n, bs) for bs in bucket_sets}
            if not any(
                np.array_equal(fut.result(), _ref(sid, b)) for b in candidates
            ):
                raise RuntimeError(
                    f"serve_adapt {tag}: request {i} (n={n}) matches no "
                    f"candidate-bucket reference {sorted(candidates)}"
                )

        thr = len(lat) / wall if wall > 0 else 0.0
        p95 = float(np.percentile(lat, 95)) if lat else float("nan")
        out[tag] = {
            "thr": thr, "p95": p95, "rej": rej, "snap": snap,
            "decisions": decisions, "buckets": rt.buckets,
            "max_batch": rt.scheduler.config.max_batch,
        }

    # (3) the controller converged: at least one actuation, with evidence
    applied = [d for d in out["adaptive"]["decisions"] if d.applied]
    if not applied:
        raise RuntimeError(
            "serve_adapt: controller applied no reconfiguration "
            f"({len(out['adaptive']['decisions'])} decisions, none actuated)"
        )
    for d in applied:
        if not d.evidence or d.version < 1 or not d.reason:
            raise RuntimeError(
                f"serve_adapt: actuated decision lacks evidence: {d}"
            )
    return out


def _drr_attempt(cfg, params, s_batch, *, n_inter, n_bulk):
    """Saturating two-class burst through a DRR-weighted queue.

    Both lanes are fully backlogged from the start, so the completion
    stream directly exposes the drain shares; returns per-class completion
    stamps and the metrics snapshot.
    """
    from repro.serve import RuntimeConfig, ServingRuntime, SLOClass

    # generous absolute + measured budget: the deadline contract must
    # assert weighted fairness, not host speed
    deadline_s = max(20.0, 60 * s_batch) * (n_inter + n_bulk) / 72
    high = SLOClass("interactive", priority=10, deadline_s=deadline_s,
                    sheddable=False)
    low = SLOClass("bulk", priority=-10, deadline_s=None, sheddable=True)
    rt = ServingRuntime(cfg, params, RuntimeConfig(
        max_batch=4,
        max_wait_s=0.005,
        max_queue=2 * (n_inter + n_bulk),
        buckets=(cfg.n_points,),
        class_weights=(("interactive", 4.0), ("bulk", 1.0)),
    ))
    rt.warmup()
    rng = np.random.default_rng(11)
    clouds = [
        rng.standard_normal((cfg.n_points, 3 + cfg.in_features)).astype(np.float32)
        for _ in range(8)
    ]
    lock = threading.Lock()
    done = []  # (class name, completion t) in completion order
    with rt:
        futs = []
        i = b = 0
        for k in range(n_inter + n_bulk):
            # 2:1 interleave keeps both lanes backlogged from the first drain
            slo = high if (k % 3 < 2 and i < n_inter) or b >= n_bulk else low
            if slo is high:
                i += 1
            else:
                b += 1

            def _rec(fut, name=slo.name):
                if fut.exception() is None:
                    with lock:
                        done.append((name, time.monotonic()))

            fut = rt.submit(clouds[k % len(clouds)], slo=slo)
            fut.add_done_callback(_rec)
            futs.append(fut)
        for f in futs:
            try:
                f.result(timeout=600)
            except Exception:  # noqa: BLE001 — expiry counted via metrics
                pass
    return done, rt.metrics.snapshot(), high


def run_adapt(smoke: bool = False, seed: int = 0) -> list[dict]:
    """Adaptive control-plane benchmark: feedback-tuned knobs vs static.

    A shifted size distribution (clouds clustered at 96-160 points, well
    below the 256-point bucket) is offered ABOVE the static runtime's
    measured capacity to a runtime pinned at a conservative max_batch=2
    and to an identical runtime with the AdaptiveController attached.  The
    controller observes full batches + a growing backlog and doubles
    max_batch through the pause-free warm-then-swap reconfiguration path
    mid-trace, amortizing the per-batch serving overhead the static
    defaults keep paying.  (Bucket tuning is deliberately off in THIS lane:
    on this backend the model's native 256-point shape is the fastest
    compiled artifact, so re-bucketing to the observed sizes cannot win
    compute here — the quantile/waste proposal math is pinned by unit
    tests instead.)  Self-asserting (raises RuntimeError, failing the CI
    bench-smoke lane):

      * the controller applied >= 1 reconfiguration, every actuated
        decision carrying evidence and a scheduler-config version;
      * every response is bitwise-equal to a direct accelerator reference
        at one of the candidate buckets (a mid-swap request may have been
        legitimately bucketed under the old or the new set);
      * no request lost or duplicated across the swap: resolved futures +
        failures + rejections == offered, and metrics agree;
      * the adapted runtime beats static in throughput OR p95 (retried
        3x — a paired open loop on a shared host is noisy; the structural
        contracts above are asserted on every attempt);
      * DRR section: under a saturating two-class burst with weights
        interactive:bulk = 4:1, the bulk class's completion share over the
        both-backlogged window is >= 0.8x its 1/5 weight share and no
        interactive deadline expires.

      serve_adapt/{static,adaptive} : us = p95; derived = thr + knob trail.
      serve_adapt/drr : us = nan; derived = measured shares vs weights.
    """
    import jax

    from repro.configs.base import get_config
    from repro.core.accelerator import get_accelerator
    from repro.serve import AdaptiveConfig

    cfg = get_config("pointnet2-cls", smoke=True)
    width = 3 + cfg.in_features
    n_points = cfg.n_points
    accel = get_accelerator(cfg)
    params = accel.init(jax.random.PRNGKey(seed))

    # batch-time calibration (for the DRR deadline budget below)
    warm = np.zeros((4, n_points, width), np.float32)
    jax.block_until_ready(accel.infer(params, warm))
    times = []
    for _ in range(5):
        t = time.perf_counter()
        jax.block_until_ready(accel.infer(params, warm))
        times.append(time.perf_counter() - t)
    s_batch = min(times)
    # pre-trace the shapes the controller's max_batch ladder will warm
    # mid-run — pool.warmup then hits the process-wide jit cache, so the
    # swap cost measured in-trace is the control path, not XLA compile time
    for b in (2, 8):
        jax.block_until_ready(
            accel.infer(params, np.zeros((b, n_points, width), np.float32))
        )

    scenes = _adapt_scene_pool(width, seed)
    # closed-loop burst probe at the STATIC knobs: the offered rate is a
    # multiple of measured end-to-end capacity (not infer time alone, which
    # undercounts the per-batch serving overhead this lane is about)
    from repro.serve import RuntimeConfig, ServingRuntime

    probe_rt = ServingRuntime(cfg, params, RuntimeConfig(
        max_batch=2, max_wait_s=0.005, max_queue=512, buckets=(n_points,),
    ))
    probe_rt.warmup()
    with probe_rt:
        t0 = time.perf_counter()
        futs = [probe_rt.submit(scenes[i % len(scenes)]) for i in range(200)]
        for f in futs:
            f.result(timeout=600)
        cap = 200 / (time.perf_counter() - t0)

    rate = 1.25 * cap  # above static capacity: backlog must build
    n_requests = int(min(4000, max(192, rate * (2.5 if smoke else 5.0))))
    order = [i % len(scenes) for i in range(n_requests)]
    ad_cfg = AdaptiveConfig(
        poll_interval_s=0.05,
        min_samples=48,
        tune_buckets=False,  # native shape is fastest here; see docstring
        tune_max_batch=True,
        max_batch_bounds=(2, 8),
        min_batch_records=8,
        tune_wait=False,
        observe_s=0.3,
        rollback_factor=3.0,  # only a real regression reverts mid-benchmark
        cooldown_s=0.2,
        min_window_completions=8,
    )

    last_err = None
    for attempt in range(3):
        arrivals = np.cumsum(
            np.random.default_rng(seed + 311 * attempt)
            .exponential(1.0 / rate, size=n_requests)
        )
        m = _adapt_attempt(cfg, params, accel, scenes, order, arrivals, ad_cfg)
        st, ad = m["static"], m["adaptive"]
        try:
            if not (ad["thr"] >= st["thr"] or ad["p95"] <= st["p95"]):
                raise RuntimeError(
                    f"serve_adapt: adapted knobs beat static in neither "
                    f"throughput ({ad['thr']:.1f} vs {st['thr']:.1f} req/s) "
                    f"nor p95 ({ad['p95'] * 1e3:.1f} vs {st['p95'] * 1e3:.1f}ms)"
                )
        except RuntimeError as e:
            last_err = e
            continue
        break
    else:
        raise RuntimeError(f"serve_adapt: failed after 3 attempts: {last_err}")

    n_applied = sum(1 for d in ad["decisions"] if d.applied)
    first = next(d for d in ad["decisions"] if d.applied)
    rows = [
        {
            "name": "serve_adapt/static",
            "us": st["p95"] * 1e6,
            "note": (
                f"{st['thr']:.1f} req/s (rate {rate:.1f}/s; p95 "
                f"{st['p95'] * 1e3:.1f}ms; rej {st['rej']}) max_batch=2 fixed"
            ),
        },
        {
            "name": "serve_adapt/adaptive",
            "us": ad["p95"] * 1e6,
            "note": (
                f"{ad['thr']:.1f} req/s (p95 {ad['p95'] * 1e3:.1f}ms; rej "
                f"{ad['rej']}) {n_applied} actuations -> max_batch="
                f"{ad['max_batch']} (first: {first.kind} {first.previous}->"
                f"{first.value}, occ {first.evidence.get('occupancy', 0):.2f}, "
                f"depth {first.evidence.get('queue_depth', 0)}); "
                f"parity bitwise-ok"
            ),
        },
        {
            "name": "serve_adapt/gain",
            "us": float("nan"),
            "note": (
                f"adaptive/static throughput {ad['thr'] / st['thr']:.2f}x, "
                f"p95 {st['p95'] / ad['p95']:.2f}x lower"
                if st["thr"] and ad["p95"] else "n/a"
            ),
        },
    ]

    # -- weighted-fair drain under saturation ---------------------------------
    n_inter, n_bulk = (48, 24) if smoke else (96, 48)
    for attempt in range(3):
        done, snap, high = _drr_attempt(
            cfg, params, s_batch, n_inter=n_inter, n_bulk=n_bulk
        )
        # both lanes stay backlogged until the interactive lane drains at
        # ~(n_inter + n_inter/4) completions; measure inside that window
        window = int(n_inter * 1.05)
        n_bulk_done = sum(1 for name, _ in done[:window] if name == "bulk")
        share = n_bulk_done / window
        hi_cls = snap.for_class(high.name)
        try:
            if hi_cls is None or hi_cls.expired or hi_cls.completed != n_inter:
                raise RuntimeError(
                    f"serve_adapt/drr: interactive deadline contract broke "
                    f"({hi_cls})"
                )
            if share < 0.8 * (1.0 / 5.0):
                raise RuntimeError(
                    f"serve_adapt/drr: bulk share {share:.2f} < 0.8x its "
                    f"1/5 weight share over the backlogged window"
                )
        except RuntimeError as e:
            last_err = e
            continue
        rows.append({
            "name": "serve_adapt/drr",
            "us": float("nan"),
            "note": (
                f"weights 4:1 -> bulk share {share:.2f} of first {window} "
                f"completions (>= {0.8 / 5:.2f}); interactive expired=0 "
                f"({n_inter}+{n_bulk} burst); attempt {attempt + 1}/3"
            ),
        })
        break
    else:
        raise RuntimeError(f"serve_adapt/drr: failed after 3 attempts: {last_err}")
    return rows
