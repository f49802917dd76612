"""Request-lifecycle tracing tests: tracer unit behavior, the closed event
registry, terminal-outcome completeness on a real runtime (every way a
request can end yields exactly one terminal event on a monotonic span),
chaos/evict/retry paths, sampling, the stage-attribution reductions, the
Prometheus exporter, the profiler stage spans, and the high-water-mark
gauges.

The integration tests reuse the SLO control-plane fixtures (real
ServingRuntime on the smoke config); the reduction tests run on synthetic
event streams with hand-picked timestamps so stage math is pinned exactly.
"""

import re
import threading
import time
from concurrent.futures import Future
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.accelerator import get_accelerator
from repro.core.policy import ExecutionPolicy
from repro.serve import (
    BULK,
    EVENTS,
    INTERACTIVE,
    TERMINAL_EVENTS,
    AdmissionQueue,
    AutoscalerConfig,
    BatchRecord,
    ChaosInjector,
    Fault,
    Reporter,
    RuntimeConfig,
    ServeMetrics,
    ServingRuntime,
    Shed,
    TraceConfig,
    TraceEvent,
    Tracer,
    batch_crosscheck,
    prometheus_text,
    request_timelines,
    stage_breakdown,
    trace_problems,
)
from repro.serve.queue import AdmissionError
from repro.serve.trace import RING_PAIRS, span

jax.config.update("jax_platform_name", "cpu")

MAX_BATCH = 4
WAIT_S = 60

SERVE_DIR = Path(__file__).resolve().parent.parent / "src" / "repro" / "serve"


@pytest.fixture(scope="module")
def cfg():
    return get_config("pointnet2-cls", smoke=True)  # n_points=256


@pytest.fixture(scope="module")
def params(cfg):
    return get_accelerator(cfg).init(jax.random.PRNGKey(0))


def _clouds(k, n=256, seed=0, width=3):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((n, width)).astype(np.float32) for _ in range(k)]


def _runtime(cfg, params, **kw):
    kw.setdefault("max_batch", MAX_BATCH)
    kw.setdefault("max_wait_s", 0.005)
    kw.setdefault("max_queue", 64)
    kw.setdefault("buckets", (cfg.n_points,))
    kw.setdefault("trace", TraceConfig())
    return ServingRuntime(cfg, params, RuntimeConfig(**kw))


def _by_trace(events):
    out = {}
    for ev in events:
        if ev.trace_id != -1:
            out.setdefault(ev.trace_id, []).append(ev)
    return out


def _assert_well_formed(events):
    """Every trace: exactly one terminal, monotonic time, no lint findings."""
    assert trace_problems(events) == []
    for tid, revs in _by_trace(events).items():
        terminals = [e.name for e in revs if e.name in TERMINAL_EVENTS]
        assert len(terminals) == 1, f"trace {tid}: terminals {terminals}"
        ts = [e.t for e in revs]
        assert ts == sorted(ts), f"trace {tid}: non-monotonic timestamps"


# -- tracer unit --------------------------------------------------------------


class TestTracerUnit:
    def test_emit_rejects_undeclared_names(self):
        tr = Tracer()
        with pytest.raises(ValueError, match="undeclared"):
            tr.emit("request.teleported")
        tr.emit("request.submit", trace_id=1)
        assert [e.name for e in tr.events()] == ["request.submit"]

    def test_ring_drops_oldest(self):
        tr = Tracer(TraceConfig(capacity=4))
        for i in range(10):
            tr.emit("request.submit", trace_id=i)
        assert len(tr) == 4
        assert tr.emitted == 10
        assert tr.dropped == 6
        assert [e.trace_id for e in tr.events()] == [6, 7, 8, 9]

    def test_clear_keeps_counting_ids(self):
        tr = Tracer()
        first = tr.new_trace()
        tr.emit("request.submit", trace_id=first)
        tr.clear()
        assert len(tr) == 0
        assert tr.new_trace() == first + 1

    def test_sampling_extremes(self):
        assert Tracer(TraceConfig(sample=0.0)).new_trace() is None
        tr = Tracer(TraceConfig(sample=1.0))
        assert [tr.new_trace() for _ in range(3)] == [1, 2, 3]

    def test_sampling_fraction_is_deterministic_and_proportional(self):
        tr_a = Tracer(TraceConfig(sample=0.5))
        tr_b = Tracer(TraceConfig(sample=0.5))
        kept_a = [tr_a.new_trace() for _ in range(400)]
        kept_b = [tr_b.new_trace() for _ in range(400)]
        assert kept_a == kept_b  # same ids -> same decisions
        frac = sum(t is not None for t in kept_a) / 400
        assert 0.3 < frac < 0.7

    def test_thread_safety_no_loss_under_capacity(self):
        tr = Tracer(TraceConfig(capacity=10_000))

        def worker(base):
            for i in range(500):
                tr.emit("request.submit", trace_id=base + i)

        threads = [threading.Thread(target=worker, args=(k * 1000,)) for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert tr.emitted == 2000
        assert tr.dropped == 0


# -- closed event-name registry ----------------------------------------------


class TestEventRegistry:
    """The event namespace is closed: grep-enforced in both directions."""

    _LIT = re.compile(
        r"""["']((?:request|batch|replica|scale|chaos|cache|adapt)\.[a-z0-9_]+)["']"""
    )

    def _literals(self):
        used = {}
        # rglob: subpackages (serve/adapt/) emit into the same registry
        for path in sorted(SERVE_DIR.rglob("*.py")):
            for name in self._LIT.findall(path.read_text()):
                # a stage span (trace.span) also emits its ring pair
                for emitted in (name, *RING_PAIRS.get(name, ())):
                    used.setdefault(emitted, set()).add(path.name)
        return used

    def test_every_emitted_name_is_declared(self):
        undeclared = {
            name: sorted(files)
            for name, files in self._literals().items()
            if name not in EVENTS
        }
        assert undeclared == {}, f"event literals not in trace.EVENTS: {undeclared}"

    def test_every_declared_name_is_emitted_somewhere(self):
        used = self._literals()
        orphans = [
            name for name in EVENTS if not (used.get(name, set()) - {"trace.py"})
        ]
        assert orphans == [], f"EVENTS entries never emitted: {orphans}"

    def test_registry_has_no_duplicates_and_terminals_are_requests(self):
        assert len(EVENTS) == len(set(EVENTS))
        assert TERMINAL_EVENTS <= set(EVENTS)
        assert all(name.startswith("request.") for name in TERMINAL_EVENTS)


# -- terminal outcomes on a real runtime --------------------------------------


class TestTerminalOutcomes:
    def test_completed_spans_are_well_formed(self, cfg, params):
        rt = _runtime(cfg, params)
        with rt:
            rt.warmup()
            futs = [rt.submit(c) for c in _clouds(8, seed=1)]
            for f in futs:
                f.result(timeout=WAIT_S)
        events = rt.tracer.events()
        _assert_well_formed(events)
        timelines = request_timelines(events)
        assert len(timelines) == 8
        for tl in timelines.values():
            assert tl.terminal == "request.completed"
            assert tl.batch_id != -1
            # the span walked the full lifecycle, in order
            names = [e.name for e in tl.events]
            assert names[0] == "request.submit"
            for a, b in (
                ("request.submit", "request.admitted"),
                ("request.admitted", "request.enqueued"),
                ("request.enqueued", "request.drained"),
                ("request.drained", "request.assembled"),
                ("request.assembled", "request.completed"),
            ):
                assert names.index(a) < names.index(b)

    def test_completed_e2e_matches_recorded_latency(self, cfg, params):
        """Acceptance: trace e2e equals the metrics latency by construction,
        and the per-stage breakdown sums to it within tolerance."""
        rt = _runtime(cfg, params)
        with rt:
            rt.warmup()
            futs = [rt.submit(c) for c in _clouds(8, seed=2)]
            for f in futs:
                f.result(timeout=WAIT_S)
        timelines = request_timelines(rt.tracer.events())
        e2es = sorted(tl.e2e_s for tl in timelines.values())
        # trace e2e starts at the runtime's request.submit emit, the metric
        # at the queue's Request.submit_t a few microseconds later; the
        # completion edge is shared by construction, so the two agree to
        # well under a millisecond
        assert np.median(e2es) == pytest.approx(
            rt.metrics.snapshot().latency_p50_s, abs=1e-3
        )
        for tl in timelines.values():
            assert tl.residual_s is not None
            # stages telescope: the unattributed residual is a small fraction
            assert tl.residual_s <= 0.25 * tl.e2e_s + 1e-3

    def test_rejected_span(self, cfg, params):
        rt = _runtime(cfg, params, max_queue=2)  # scheduler never started
        try:
            clouds = _clouds(1)
            rt.submit(clouds[0])
            rt.submit(clouds[0])
            with pytest.raises(AdmissionError):
                rt.submit(clouds[0])
            events = rt.tracer.events()
            _assert_well_formed([e for e in events if e.trace_id == 3])
            rejected = [e for e in events if e.name == "request.rejected"]
            assert len(rejected) == 1
            assert rejected[0].args["reason"] == "queue_full"
        finally:
            rt.stop(drain=False)

    def test_shed_at_admission_span(self, cfg, params):
        rt = _runtime(cfg, params, max_queue=16, shed_threshold=2)
        try:
            clouds = _clouds(1)
            rt.submit(clouds[0], slo=BULK)
            rt.submit(clouds[0], slo=BULK)
            with pytest.raises(Shed):
                rt.submit(clouds[0], slo=BULK)
            shed = [e for e in rt.tracer.events() if e.name == "request.shed"]
            assert len(shed) == 1
            assert shed[0].args["reason"] == "admission"
            assert shed[0].slo == "bulk"
        finally:
            rt.stop(drain=False)

    def test_shed_by_eviction_span(self, cfg, params):
        rt = _runtime(cfg, params, max_queue=2)
        try:
            clouds = _clouds(1)
            rt.submit(clouds[0], slo=BULK)
            victim = rt.submit(clouds[0], slo=BULK)
            rt.submit(clouds[0], slo=INTERACTIVE)  # full: evicts newest bulk
            with pytest.raises(Shed):
                victim.result(timeout=WAIT_S)
            events = rt.tracer.events()
            shed = [e for e in events if e.name == "request.shed"]
            assert len(shed) == 1
            assert shed[0].args["reason"] == "evicted"
            assert shed[0].trace_id == 2  # the second submit was the victim
            _assert_well_formed([e for e in events if e.trace_id == 2])
        finally:
            rt.stop(drain=False)

    def test_expired_span(self, cfg, params):
        rt = _runtime(cfg, params, max_wait_s=0.2)
        with rt:
            fut = rt.submit(_clouds(1)[0], timeout_s=0.0)
            with pytest.raises(Exception):  # noqa: B017 — DeadlineExceeded
                fut.result(timeout=WAIT_S)
        events = rt.tracer.events()
        _assert_well_formed(events)
        assert [e.name for e in events if e.name in TERMINAL_EVENTS] == [
            "request.expired"
        ]

    def test_failed_span(self, cfg, params):
        """A batch whose execution future fails ends every member span in
        exactly one request.failed (plus a batch.failed on the batch span)."""
        rt = _runtime(cfg, params)

        def failing_dispatch(mb):
            fut = Future()
            fut.set_exception(RuntimeError("device on fire"))
            return fut

        rt.scheduler.dispatch_fn = failing_dispatch
        with rt:
            futs = [rt.submit(c) for c in _clouds(3, seed=3)]
            for f in futs:
                with pytest.raises(RuntimeError, match="device on fire"):
                    f.result(timeout=WAIT_S)
        events = rt.tracer.events()
        _assert_well_formed(events)
        assert sum(e.name == "request.failed" for e in events) == 3
        assert sum(e.name == "batch.failed" for e in events) >= 1


# -- chaos / evict / retry paths ----------------------------------------------


class TestChaosAndRetryTracing:
    def test_kill_evict_retry_completes_all_spans(self, cfg, params):
        """Chaos kill mid-trace: the stream shows chaos.kill,
        replica.evicted, batch.retry and a rejoin — and every request span
        still ends in exactly one request.completed."""
        rt = _runtime(
            cfg, params,
            n_replicas=2,
            autoscaler=AutoscalerConfig(
                poll_interval_s=0.02, rejoin_delay_s=0.05, cooldown_s=60.0
            ),
        )
        rt.warmup()
        ChaosInjector([Fault(replica_id=1, at_batch=1, kind="kill")]).attach(rt.pool)
        with rt:
            futs = [rt.submit(c) for c in _clouds(24, seed=11)]
            for f in futs:
                f.result(timeout=WAIT_S)
            deadline = time.monotonic() + WAIT_S
            while rt.metrics.rejoins < 1 and time.monotonic() < deadline:
                time.sleep(0.02)
        events = rt.tracer.events()
        _assert_well_formed(events)
        names = [e.name for e in events]
        assert names.count("chaos.kill") == 1
        assert "replica.evicted" in names
        assert "batch.retry" in names
        assert "scale.rejoin" in names and "replica.rejoin" in names
        kill = next(e for e in events if e.name == "chaos.kill")
        assert kill.replica_id == 1 and kill.batch_id != -1
        # every span completed despite the fault
        terminals = [e.name for e in events if e.name in TERMINAL_EVENTS]
        assert set(terminals) == {"request.completed"}
        assert len(terminals) == 24

    def test_wedge_eviction_traced(self, cfg, params):
        rt = _runtime(
            cfg, params,
            n_replicas=2,
            heartbeat_timeout_s=0.25,
            autoscaler=AutoscalerConfig(poll_interval_s=0.02, rejoin_delay_s=0.05),
        )
        rt.warmup()
        ChaosInjector(
            [Fault(replica_id=0, at_batch=0, kind="wedge", duration_s=1.0)]
        ).attach(rt.pool)
        with rt:
            futs = [rt.submit(c) for c in _clouds(8, seed=13)]
            for f in futs:
                f.result(timeout=WAIT_S)
            deadline = time.monotonic() + WAIT_S
            while rt.metrics.rejoins < 1 and time.monotonic() < deadline:
                time.sleep(0.02)
        events = rt.tracer.events()
        _assert_well_formed(events)
        names = [e.name for e in events]
        assert "chaos.wedge" in names
        assert "replica.evicted" in names
        terminals = [e.name for e in events if e.name in TERMINAL_EVENTS]
        assert set(terminals) == {"request.completed"} and len(terminals) == 8


# -- cache-path stage events --------------------------------------------------


class TestCacheTracing:
    def test_hits_trace_cache_and_feature_stages(self, cfg, params):
        """Duplicate clouds: the repeat batch shows cache hit probes and an
        all-hit cache_end(skip=True) followed by a feature stage — the
        preprocess stage is absent, matching the skip the cache promises."""
        rt = _runtime(cfg, params, cache_max_bytes=64 * 2**20)
        clouds = _clouds(MAX_BATCH, seed=5)
        with rt:
            rt.warmup()
            for f in [rt.submit(c) for c in clouds]:  # cold: misses + insert
                f.result(timeout=WAIT_S)
            # the cache fill is a background insert on the replica thread;
            # wait for it so the warm round probes a populated cache
            deadline = time.monotonic() + WAIT_S
            while (
                rt.cache.stats().insertions < len(clouds)
                and time.monotonic() < deadline
            ):
                time.sleep(0.01)
            for f in [rt.submit(c) for c in clouds]:  # warm: all hits
                f.result(timeout=WAIT_S)
        events = rt.tracer.events()
        _assert_well_formed(events)
        names = [e.name for e in events]
        assert "cache.insert" in names
        lookups = [e for e in events if e.name == "request.cache_lookup"]
        assert any(e.args["hit"] for e in lookups)
        assert any(not e.args["hit"] for e in lookups)
        skips = [
            e for e in events
            if e.name == "batch.cache_end" and e.args and e.args.get("skip")
        ]
        assert skips, "no all-hit batch traced a cache_end(skip=True)"
        skip_bid = skips[0].batch_id
        batch_names = {e.name for e in events if e.batch_id == skip_bid}
        assert "batch.feature_start" in batch_names
        assert "batch.preprocess_start" not in batch_names


# -- sampling -----------------------------------------------------------------


class TestSampling:
    def test_sample_zero_keeps_batch_events_only(self, cfg, params):
        rt = _runtime(cfg, params, trace=TraceConfig(sample=0.0))
        with rt:
            rt.warmup()
            for f in [rt.submit(c) for c in _clouds(4, seed=7)]:
                f.result(timeout=WAIT_S)
        events = rt.tracer.events()
        assert events, "batch/control events must flow even at sample=0"
        assert all(not e.name.startswith("request.") for e in events)
        assert all(e.trace_id == -1 for e in events)
        # the batch frame of reference is intact
        assert any(e.name == "batch.assembled" for e in events)
        members = next(e for e in events if e.name == "batch.assembled").args[
            "members"
        ]
        assert members == []  # no sampled members to link

    def test_sample_one_traces_every_request(self, cfg, params):
        rt = _runtime(cfg, params, trace=TraceConfig(sample=1.0))
        with rt:
            rt.warmup()
            for f in [rt.submit(c) for c in _clouds(4, seed=7)]:
                f.result(timeout=WAIT_S)
        assert len(request_timelines(rt.tracer.events())) == 4


# -- high-water marks + straggler attribution ---------------------------------


class TestGauges:
    def test_queue_hwm_sees_bursts_between_drains(self):
        m = ServeMetrics()
        q = AdmissionQueue(16, metrics=m)
        clouds = np.zeros((8, 3), np.float32)
        from repro.core.policy import ExecutionPolicy

        for _ in range(5):
            q.submit(clouds, bucket=256, policy=ExecutionPolicy(), slo=BULK)
        q.drain(16, timeout_s=1.0)  # queue is empty again...
        snap = m.snapshot()
        assert snap.queue_depth_hwm == 5  # ...but the mark remembers the burst
        assert snap.for_class("bulk").depth_hwm == 5

    def test_inflight_hwm_monotonic(self):
        m = ServeMetrics()
        m.record_inflight(2)
        m.record_inflight(5)
        m.record_inflight(1)
        assert m.snapshot().inflight_hwm == 5

    def test_runtime_populates_hwms(self, cfg, params):
        rt = _runtime(cfg, params)
        with rt:
            rt.warmup()
            for f in [rt.submit(c) for c in _clouds(8, seed=9)]:
                f.result(timeout=WAIT_S)
        snap = rt.metrics.snapshot()
        assert snap.queue_depth_hwm >= 1
        assert snap.inflight_hwm >= 1

    def test_straggler_attribution(self):
        class _Ev:
            duration_s, median_s, ratio = 0.5, 0.1, 5.0

        m = ServeMetrics()
        m.record_straggler(_Ev(), replica_id=2)
        m.record_straggler(_Ev(), replica_id=2)
        m.record_straggler(_Ev(), replica_id=0)
        snap = m.snapshot()
        assert snap.straggler_events == 3
        assert snap.stragglers_by_replica == ((0, 1), (2, 2))

    def test_pool_straggler_hook_emits_event(self, cfg, params):
        from repro.serve import ReplicaPool

        class _Ev:
            duration_s, median_s, ratio = 0.5, 0.1, 5.0

        metrics = ServeMetrics()
        tracer = Tracer()
        pool = ReplicaPool(
            cfg, params, n_replicas=1, metrics=metrics, tracer=tracer
        )
        try:
            pool._on_straggler(0, _Ev())
        finally:
            pool.shutdown()
        assert metrics.snapshot().stragglers_by_replica == ((0, 1),)
        straggles = [e for e in tracer.events() if e.name == "replica.straggler"]
        assert len(straggles) == 1
        assert straggles[0].replica_id == 0
        assert straggles[0].args["ratio"] == 5.0


# -- reductions on synthetic streams ------------------------------------------


def _synthetic_stream():
    """One hand-timed request through every sequential stage."""
    t = {
        "submit": 1.00, "admitted": 1.001, "enqueued": 1.002, "drained": 1.10,
        "assembled": 1.15, "exec0": 1.20, "exec1": 1.70, "completed": 1.75,
    }
    return [
        TraceEvent("request.submit", t["submit"], trace_id=1, slo="default"),
        TraceEvent("request.admitted", t["admitted"], trace_id=1, slo="default"),
        TraceEvent("request.enqueued", t["enqueued"], trace_id=1, slo="default"),
        TraceEvent("request.drained", t["drained"], trace_id=1, slo="default"),
        TraceEvent("batch.assembled", t["assembled"], batch_id=7, args={"members": [1]}),
        TraceEvent("request.assembled", t["assembled"], trace_id=1, batch_id=7),
        TraceEvent("batch.dispatched", 1.16, batch_id=7, replica_id=0),
        TraceEvent("batch.execute_start", t["exec0"], batch_id=7),
        TraceEvent("batch.execute_end", t["exec1"], batch_id=7),
        TraceEvent("request.completed", t["completed"], trace_id=1, batch_id=7),
        TraceEvent("batch.completed", 1.76, batch_id=7),
    ]


class TestReductions:
    def test_stage_math_is_exact(self):
        tl = request_timelines(_synthetic_stream())[1]
        assert tl.terminal == "request.completed"
        assert tl.e2e_s == pytest.approx(0.75)
        assert tl.stages["queue"] == pytest.approx(0.10)
        assert tl.stages["assembly"] == pytest.approx(0.05)
        assert tl.stages["dispatch"] == pytest.approx(0.05)
        assert tl.stages["execute"] == pytest.approx(0.50)
        assert tl.stages["finalize"] == pytest.approx(0.05)
        assert tl.residual_s == pytest.approx(0.0)

    def test_trace_problems_flags_malformed(self):
        good = _synthetic_stream()
        assert trace_problems(good) == []
        no_terminal = [e for e in good if e.name != "request.completed"]
        assert trace_problems(no_terminal) == ["trace 1: no terminal event"]
        double = good + [TraceEvent("request.failed", 1.8, trace_id=1)]
        assert "multiple terminals" in trace_problems(double)[0]
        regressed = good[:1] + [TraceEvent("request.drained", 0.5, trace_id=1)]
        assert any("regressed" in p for p in trace_problems(regressed))

    def test_truncated_head_is_skipped(self):
        tail = [e for e in _synthetic_stream() if e.name != "request.submit"]
        assert trace_problems(tail) == []  # ring overflow is not a violation

    def test_stage_breakdown_percentiles(self):
        stream = _synthetic_stream()
        bd = stage_breakdown(stream)
        assert bd.counts == {"default": 1}
        p50, p95 = bd.per_class["default"]["execute"]
        assert p50 == pytest.approx(0.50) and p95 == pytest.approx(0.50)
        assert "execute" in bd.format_rows()

    def test_batch_crosscheck(self):
        rec = BatchRecord(
            bucket=256, policy_key=("fp32", "jax", "sequential"), n_real=1,
            batch_size=4, replica_id=0, duration_s=0.50, batch_id=7,
        )
        checks = batch_crosscheck(_synthetic_stream(), (rec,))
        assert len(checks) == 1
        assert checks[0].span_s == pytest.approx(0.50)
        assert checks[0].rel_err == pytest.approx(0.0)
        # records without a span (or untraced) are skipped, not crashed
        assert batch_crosscheck([], (rec,)) == []

    def test_crosscheck_on_real_run(self, cfg, params):
        """Acceptance: trace spans reconcile with the independently-timed
        BatchRecord wall clock on a live sequential run."""
        rt = _runtime(cfg, params)
        with rt:
            rt.warmup()
            for f in [rt.submit(c) for c in _clouds(8, seed=21)]:
                f.result(timeout=WAIT_S)
        checks = batch_crosscheck(rt.tracer.events(), rt.metrics.batch_records)
        assert checks, "no batch reconciled"
        assert all(c.rel_err < 0.5 for c in checks)


# -- exporters ----------------------------------------------------------------


class TestExporters:
    def test_prometheus_text_shape(self):
        m = ServeMetrics()
        m.record_submitted("interactive")
        m.record_completed(0.01, "interactive")
        m.record_straggler(None, replica_id=1)
        m.record_queue_hwm(7, "interactive", 7)
        text = prometheus_text(m.snapshot())
        assert text.endswith("\n")
        assert "pc2im_serve_submitted_total 1" in text
        assert 'pc2im_serve_latency_seconds{quantile="0.5"}' in text
        assert 'pc2im_serve_stragglers_total{replica="1"} 1' in text
        assert 'pc2im_serve_class_completed_total{slo="interactive"} 1' in text
        assert "pc2im_serve_queue_depth_hwm 7" in text
        # HELP/TYPE precede every family exactly once
        for line in text.splitlines():
            if line.startswith("pc2im_serve_submitted_total"):
                idx = text.splitlines().index(line)
                assert text.splitlines()[idx - 1].startswith("# TYPE")
                assert text.splitlines()[idx - 2].startswith("# HELP")
                break


# -- reporter -----------------------------------------------------------------


class TestReporter:
    def test_interval_validation(self):
        with pytest.raises(ValueError, match="interval_s"):
            Reporter(ServeMetrics(), 0.0)

    def test_report_once_and_sink(self):
        lines = []
        m = ServeMetrics()
        m.record_submitted()
        m.record_completed(0.01)
        rep = Reporter(m, 10.0, sink=lines.append, tracer=Tracer())
        line = rep.report_once()
        assert lines == [line]
        assert line.startswith("[serve] completed=1")
        assert "trace=0ev" in line
        assert rep.last_snapshot.completed == 1

    def test_thread_ticks_and_final_report(self):
        lines = []
        rep = Reporter(ServeMetrics(), 0.02, sink=lines.append)
        rep.start()
        time.sleep(0.1)
        rep.stop()
        assert rep.ticks >= 2  # periodic ticks plus the final flush
        assert len(lines) == rep.ticks

    def test_runtime_owns_reporter(self, cfg, params):
        rt = _runtime(cfg, params, report_interval_s=30.0)
        assert rt.reporter is not None
        with rt:
            rt.warmup()
            rt.submit(_clouds(1)[0]).result(timeout=WAIT_S)
        # stop() flushed a final tick with the end-state snapshot
        assert rt.reporter.last_snapshot is not None
        assert rt.reporter.last_snapshot.completed == 1


# -- off is off ---------------------------------------------------------------


class TestTracingOff:
    def test_no_tracer_anywhere_by_default(self, cfg, params):
        rt = ServingRuntime(
            cfg, params,
            RuntimeConfig(max_batch=MAX_BATCH, buckets=(cfg.n_points,)),
        )
        try:
            assert rt.tracer is None
            assert rt.queue.tracer is None
            assert rt.scheduler.tracer is None
            assert rt.pool.tracer is None
            assert rt.reporter is None
        finally:
            rt.stop(drain=False)

    def test_untraced_run_still_serves(self, cfg, params):
        rt = ServingRuntime(
            cfg, params,
            RuntimeConfig(max_batch=MAX_BATCH, buckets=(cfg.n_points,)),
        )
        with rt:
            rt.warmup()
            out = rt.submit(_clouds(1)[0]).result(timeout=WAIT_S)
        assert out.shape == (cfg.n_classes,)


# -- profiler stage spans ------------------------------------------------------


class TestStageSpans:
    def test_span_rejects_undeclared_names(self):
        with pytest.raises(ValueError, match="undeclared"):
            with span("batch.teleport"):
                pass

    def test_span_with_a_ring_pair_emits_it_with_end_args(self):
        tr = Tracer()
        with span("batch.cache", tr, batch_id=3, replica_id=1) as end:
            end["hits"] = 2
        evs = tr.events()
        assert [e.name for e in evs] == ["batch.cache_start", "batch.cache_end"]
        assert all(e.batch_id == 3 and e.replica_id == 1 for e in evs)
        assert evs[0].args is None and evs[1].args == {"hits": 2}

    def test_profiler_only_spans_and_warmup_batches_emit_no_ring_events(self):
        tr = Tracer()
        with span("batch.h2d", tr, batch_id=3, replica_id=0):
            pass
        with span("batch.execute", tr, batch_id=-1, replica_id=0):
            pass
        with span("batch.execute"):  # no tracer attached
            pass
        assert tr.events() == []

    def test_failed_block_emits_no_end_event(self):
        tr = Tracer()
        with pytest.raises(RuntimeError):
            with span("batch.feature", tr, batch_id=5, replica_id=0):
                raise RuntimeError("device fault")
        assert [e.name for e in tr.events()] == ["batch.feature_start"]

    @pytest.mark.parametrize("path", ["sequential", "pipelined", "cached"])
    def test_served_batch_stages_on_the_profiler_host_plane(
        self, cfg, params, tmp_path, path
    ):
        """A served batch's stage spans reach a CPU profiler session in order,
        each carrying the replica id."""
        from jax.profiler import ProfileData

        policy = ExecutionPolicy(pipeline="pipelined") if path == "pipelined" else None
        kw = {"cache_max_bytes": 1 << 24} if path == "cached" else {}
        rt = _runtime(cfg, params, max_wait_s=WAIT_S, **kw)
        rt.warmup(policies=(policy,))
        with rt:
            jax.profiler.start_trace(str(tmp_path))
            try:
                futs = [rt.submit(c, policy=policy) for c in _clouds(MAX_BATCH, seed=9)]
                for f in futs:
                    f.result(timeout=WAIT_S)
            finally:
                jax.profiler.stop_trace()
        (xplane,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
        spans = {}
        for plane in ProfileData.from_file(str(xplane)).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("batch."):
                        spans.setdefault(e.name, []).append((e.start_ns, dict(e.stats)))
        order = ["batch.assemble", "batch.h2d", "batch.launch", "batch.wait",
                 "batch.d2h", "batch.complete"]
        assert all(len(spans.get(n, ())) >= 1 for n in order), sorted(spans)
        starts = [min(t for t, _ in spans[n]) for n in order]
        assert starts == sorted(starts), dict(zip(order, starts))
        for name in order[1:]:
            assert {st.get("replica") for _, st in spans[name]} == {0}, name
        ring = {"pipelined": "batch.feature", "cached": "batch.cache"}
        assert ring.get(path, "batch.execute") in spans
