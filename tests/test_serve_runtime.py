"""Serving-runtime subsystem tests: queue backpressure, deadline expiry,
scheduler bitwise parity, mixed-policy isolation, replica health/eviction,
and accelerator-cache introspection under concurrent traffic.

Everything runs on the smoke config with ONE static shape family
(max_batch=4, bucket 256) so all tests share the same jit traces; the
threaded tests bound every wait with explicit future timeouts, so they fail
fast rather than hang on a bare environment (CI additionally runs pytest
under pytest-timeout).
"""

import concurrent.futures
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _timing import time_mult, wait_until

from repro.configs.base import get_config
from repro.core import accelerator as accel_mod
from repro.core.accelerator import cache_stats, clear_cache, get_accelerator
from repro.core.policy import ExecutionPolicy, resolve_policy
from repro.serve import (
    AdmissionQueue,
    DeadlineExceeded,
    MicroBatch,
    QueueFull,
    ReplicaPool,
    RuntimeConfig,
    ServeMetrics,
    ServingRuntime,
    assemble_batch,
    bucket_for,
    scatter_results,
)
from repro.serve.queue import Request

jax.config.update("jax_platform_name", "cpu")

MAX_BATCH = 4
# bound on every future/result wait: fail, never hang.  Scaled by
# PC2IM_TEST_TIME_MULT (tests/_timing.py) for saturated CI hosts.
WAIT_S = 60 * time_mult()


@pytest.fixture(scope="module")
def cfg():
    return get_config("pointnet2-cls", smoke=True)  # n_points=256


@pytest.fixture(scope="module")
def params(cfg):
    return get_accelerator(cfg).init(jax.random.PRNGKey(0))


def _clouds(k, sizes=(256,), seed=0, width=3):
    rng = np.random.default_rng(seed)
    return [
        rng.standard_normal((sizes[i % len(sizes)], width)).astype(np.float32)
        for i in range(k)
    ]


def _runtime(cfg, params, **kw):
    kw.setdefault("max_batch", MAX_BATCH)
    kw.setdefault("max_wait_s", 0.005)
    kw.setdefault("max_queue", 64)
    kw.setdefault("buckets", (cfg.n_points,))
    return ServingRuntime(cfg, params, RuntimeConfig(**kw))


class TestAdmissionQueue:
    def test_backpressure_rejects_with_reason(self):
        q = AdmissionQueue(max_depth=2)
        pol = ExecutionPolicy()
        cloud = np.zeros((8, 3), np.float32)
        q.submit(cloud, bucket=256, policy=pol)
        q.submit(cloud, bucket=256, policy=pol)
        with pytest.raises(QueueFull) as exc:
            q.submit(cloud, bucket=256, policy=pol)
        assert exc.value.reason == "queue_full"
        assert exc.value.depth == 2 and exc.value.max_depth == 2
        assert q.depth() == 2  # rejected request never entered

    def test_drain_fifo_and_close(self):
        q = AdmissionQueue(max_depth=8)
        pol = ExecutionPolicy()
        for i in range(3):
            q.submit(np.full((4, 3), i, np.float32), bucket=256, policy=pol)
        got = q.drain(max_items=2, timeout_s=0.01)
        assert [r.cloud[0, 0] for r in got] == [0.0, 1.0]
        left = q.close()
        assert [r.cloud[0, 0] for r in left] == [2.0]
        with pytest.raises(Exception, match="closed"):
            q.submit(np.zeros((4, 3), np.float32), bucket=256, policy=pol)
        assert q.drain(max_items=4, timeout_s=0.01) == []

    def test_runtime_backpressure_counts_rejections(self, cfg, params):
        rt = _runtime(cfg, params, max_queue=2)  # never started: queue fills
        try:
            rt.submit(_clouds(1)[0])
            rt.submit(_clouds(1)[0])
            with pytest.raises(QueueFull):
                rt.submit(_clouds(1)[0])
            assert rt.metrics.rejected == 1
            assert rt.metrics.submitted == 2
        finally:
            rt.stop(drain=False)
            rt.pool.shutdown()


class TestDeadlines:
    def test_expired_request_fails_future(self, cfg, params):
        rt = _runtime(cfg, params)
        # submit BEFORE starting the scheduler: the deadline (now+0) is
        # already past when the drain loop first sees the request
        fut_dead = rt.submit(_clouds(1)[0], timeout_s=0.0)
        fut_live = rt.submit(_clouds(1, seed=1)[0])  # no deadline
        with rt:
            out = fut_live.result(timeout=WAIT_S)
            with pytest.raises(DeadlineExceeded):
                fut_dead.result(timeout=WAIT_S)
        assert out.shape == (cfg.n_classes,)
        assert rt.metrics.expired == 1
        assert rt.metrics.completed == 1


class TestSchedulerParity:
    def test_bitwise_identical_to_direct_infer(self, cfg, params):
        """Scheduler output == direct accel.infer on the same padded batch,
        bitwise (the acceptance criterion for scheduler correctness)."""
        clouds = _clouds(3, sizes=(256, 150, 300), seed=2)
        rt = _runtime(cfg, params)
        futs = [rt.submit(c) for c in clouds]  # queued pre-start: one batch
        with rt:
            outs = [f.result(timeout=WAIT_S) for f in futs]

        accel = get_accelerator(cfg)
        reqs = [
            Request(id=i, cloud=c, n_orig=c.shape[0], bucket=256,
                    policy=rt.default_policy, deadline_t=None, submit_t=0.0,
                    future=None)
            for i, c in enumerate(clouds)
        ]
        batch = assemble_batch(reqs, bucket=256, width=3, max_batch=MAX_BATCH)
        direct = np.asarray(accel.infer(params, jnp.asarray(batch)))
        for i, out in enumerate(outs):
            np.testing.assert_array_equal(out, direct[i])
        # and it really was one micro-batch of 3 on one replica
        real = [b for b in rt.metrics.batch_records if b.n_real]
        assert len(real) == 1 and real[0].n_real == 3

    def test_bucketing_routes_to_smallest_fit(self):
        assert bucket_for(100, (192, 256)) == 192
        assert bucket_for(192, (192, 256)) == 192
        assert bucket_for(193, (192, 256)) == 256
        assert bucket_for(999, (192, 256)) == 256  # oversized -> largest

    def test_seg_scatter_maps_rows_back(self):
        """scatter_results drops padding rows and maps subsampled clouds back
        to every original row via the exact inverse."""
        from repro.serve.pointcloud import inverse_subsample_indices

        small = np.zeros((100, 3), np.float32)
        big = np.zeros((300, 3), np.float32)
        reqs = [
            Request(id=0, cloud=small, n_orig=100, bucket=256, policy=None,
                    deadline_t=None, submit_t=0.0, future=None),
            Request(id=1, cloud=big, n_orig=300, bucket=256, policy=None,
                    deadline_t=None, submit_t=0.0, future=None),
        ]
        mb = MicroBatch(requests=tuple(reqs), bucket=256, policy=None,
                        batch=np.zeros((4, 256, 3), np.float32))
        logits = np.arange(4 * 256, dtype=np.float32).reshape(4, 256)[..., None]
        outs = scatter_results("seg", logits, mb)
        np.testing.assert_array_equal(outs[0], logits[0, :100])
        np.testing.assert_array_equal(
            outs[1], logits[1, inverse_subsample_indices(300, 256)]
        )


class TestMixedPolicies:
    def test_batches_never_share_an_artifact(self, cfg, params):
        """Interleaved fp32 / SC W16A16 traffic: every executed micro-batch
        carries exactly one policy, results match that policy's direct
        artifact bitwise, and the accelerator cache holds one artifact per
        policy (no compile storm)."""
        clear_cache()
        quant = ExecutionPolicy(quant="sc_w16a16")
        clouds = _clouds(8, seed=3)
        # the requests are submitted before start(): with the 5 ms default
        # patience a slow first drain would find them past max_wait and
        # flush half-full batches.  Only full batches may flush here.
        rt = _runtime(cfg, params, max_wait_s=WAIT_S)
        futs = [
            rt.submit(c, policy=quant if i % 2 else None)
            for i, c in enumerate(clouds)
        ]
        with rt:
            outs = [f.result(timeout=WAIT_S) for f in futs]

        records = [b for b in rt.metrics.batch_records if b.n_real]
        assert len(records) == 2  # one full batch per policy, never mixed
        assert {r.policy_key[0] for r in records} == {"none", "sc_w16a16"}
        assert all(r.n_real == MAX_BATCH for r in records)

        stats = cache_stats()
        assert stats.size == 2
        assert sorted(q for _, q, *_ in stats.keys) == ["none", "sc_w16a16"]

        for pol, idxs in ((None, (0, 2, 4, 6)), (quant, (1, 3, 5, 7))):
            accel = get_accelerator(cfg, pol)
            reqs = [
                Request(id=i, cloud=clouds[i], n_orig=256, bucket=256,
                        policy=resolve_policy(cfg, pol), deadline_t=None,
                        submit_t=0.0, future=None)
                for i in idxs
            ]
            batch = assemble_batch(reqs, 256, 3, MAX_BATCH)
            direct = np.asarray(accel.infer(params, jnp.asarray(batch)))
            for j, i in enumerate(idxs):
                np.testing.assert_array_equal(outs[i], direct[j])

    def test_concurrent_submitters_one_artifact_per_policy(self, cfg, params):
        """8 submitter threads x 2 policies hammering one runtime: the cache
        must end at exactly 2 artifacts (construction is lock-serialised)."""
        clear_cache()
        quant = ExecutionPolicy(quant="sc_w16a16")
        rt = _runtime(cfg, params, max_queue=128)
        clouds = _clouds(32, seed=4)
        with rt:
            with concurrent.futures.ThreadPoolExecutor(max_workers=8) as ex:
                futs = list(ex.map(
                    lambda i: rt.submit(clouds[i], policy=quant if i % 2 else None),
                    range(32),
                ))
            outs = [f.result(timeout=WAIT_S) for f in futs]
        assert all(o.shape == (cfg.n_classes,) for o in outs)
        stats = cache_stats()
        assert stats.size == 2, stats
        assert stats.misses == 2, stats

    def test_preprocess_cache_isolated_per_policy(self, cfg, params):
        """The SAME cloud served under two policies must key two DIFFERENT
        preprocess-cache entries: a result cached under one (quant, backend,
        pipeline) key is never served to another policy, and each policy's
        hit stays bitwise-equal to that policy's own artifact."""
        quant = ExecutionPolicy(quant="sc_w16a16")
        clouds = _clouds(MAX_BATCH, seed=9)
        rt = _runtime(cfg, params, cache_max_bytes=64 * 2**20)
        with rt:
            fp32_1 = [rt.infer(c) for c in clouds]
            # same clouds, different policy: must MISS (not reuse fp32
            # neighborhoods computed under the fp32 artifact's backend)
            q_1 = [rt.infer(c, policy=quant) for c in clouds]
            fp32_2 = [rt.infer(c) for c in clouds]
            q_2 = [rt.infer(c, policy=quant) for c in clouds]
            stats = rt.cache_stats()

        assert stats.entries == 2 * len(clouds), stats  # one entry per policy
        assert stats.misses >= 2 * len(clouds), stats

        # direct reference with the SAME batch composition the blocking
        # serial submits produced (one real row + zero filler)
        for pol, outs in ((None, fp32_1 + fp32_2), (quant, q_1 + q_2)):
            accel = get_accelerator(cfg, pol)
            resolved = resolve_policy(cfg, pol)
            for i, out in enumerate(outs):
                req = Request(id=i, cloud=clouds[i % len(clouds)], n_orig=256,
                              bucket=256, policy=resolved, deadline_t=None,
                              submit_t=0.0, future=None)
                batch = assemble_batch([req], 256, 3, MAX_BATCH)
                direct = np.asarray(accel.infer(params, jnp.asarray(batch)))[0]
                np.testing.assert_array_equal(out, direct)
        # the two policies produce different logits on this traffic — if a
        # cached result ever crossed policies the equality above would fail,
        # but make the premise explicit
        assert not np.array_equal(fp32_1[0], q_1[0])


class TestReplicaPool:
    def _mb(self, cfg, policy=None):
        return MicroBatch(
            requests=(),
            bucket=cfg.n_points,
            policy=resolve_policy(cfg, policy),
            batch=np.zeros((MAX_BATCH, cfg.n_points, 3), np.float32),
        )

    def test_least_loaded_spreads_across_replicas(self, cfg, params):
        pool = ReplicaPool(cfg, params, n_replicas=2, metrics=ServeMetrics())
        try:
            futs = [pool.submit(self._mb(cfg)) for _ in range(4)]
            for f in futs:
                assert f.result(timeout=WAIT_S).shape == (MAX_BATCH, cfg.n_classes)
            used = {b.replica_id for b in pool.metrics.batch_records}
            assert used == {0, 1}
        finally:
            pool.shutdown()

    def test_eviction_on_dead_heartbeat_retries_inflight(self, cfg, params):
        """A wedged replica (simulated hung worker) misses heartbeats, gets
        evicted, and its in-flight batch is re-dispatched to the survivor."""
        metrics = ServeMetrics()
        pool = ReplicaPool(
            cfg, params, n_replicas=2, heartbeat_timeout_s=0.25,
            max_retries=2, metrics=metrics,
        )
        try:
            # wedge replica 0's single worker thread (ties inflight=0 break
            # toward the lowest id, so the next batch queues behind the hang)
            pool.replicas[0].submit(time.sleep, 2.0)
            fut = pool.submit(self._mb(cfg))
            out = fut.result(timeout=WAIT_S)  # completes via the survivor
            assert out.shape == (MAX_BATCH, cfg.n_classes)
            deadline = time.monotonic() + WAIT_S
            while pool.replicas[0].alive and time.monotonic() < deadline:
                time.sleep(0.01)
            assert not pool.replicas[0].alive
            assert pool.replicas[1].alive
            assert metrics.evictions == 1
            assert metrics.retries >= 1
            assert [b.replica_id for b in metrics.batch_records if b.n_real == 0] == [1]
            # pool keeps serving on the survivor
            assert pool.submit(self._mb(cfg)).result(timeout=WAIT_S) is not None
        finally:
            pool.shutdown()

    def test_all_replicas_dead_fails_future(self, cfg, params):
        pool = ReplicaPool(cfg, params, n_replicas=1, metrics=ServeMetrics())
        try:
            pool.evict(0, reason="test")
            fut = pool.submit(self._mb(cfg))
            with pytest.raises(Exception, match="replica"):
                fut.result(timeout=WAIT_S)
        finally:
            pool.shutdown()


class TestRobustness:
    """One bad request (or client) must never wedge the runtime for the
    good ones — regressions found in review."""

    def test_empty_cloud_rejected_at_submit(self, cfg, params):
        rt = _runtime(cfg, params)
        try:
            with pytest.raises(ValueError, match="n >= 1"):
                rt.submit(np.zeros((0, 3), np.float32))
            with pytest.raises(ValueError):
                rt.submit(np.zeros((4, 5), np.float32))  # wrong width
        finally:
            rt.stop(drain=False)

    def test_cancelled_future_does_not_kill_scheduler(self, cfg, params):
        rt = _runtime(cfg, params)
        fut_dead = rt.submit(_clouds(1)[0], timeout_s=0.0)
        assert fut_dead.cancel()  # client walks away while still queued
        fut_live = rt.submit(_clouds(1, seed=6)[0])
        with rt:
            out = fut_live.result(timeout=WAIT_S)  # scheduler survived
        assert out.shape == (cfg.n_classes,)
        assert rt.metrics.expired == 0  # cancelled, not expired

    def test_stop_without_start_cancels_and_closes(self, cfg, params):
        rt = _runtime(cfg, params)
        fut = rt.submit(_clouds(1)[0])
        rt.stop()  # never started: nothing could ever complete this
        assert fut.cancelled()
        with pytest.raises(Exception, match="closed"):
            rt.submit(_clouds(1)[0])

    def test_deadline_expiring_in_pending_is_shed(self, cfg, params):
        """A deadline that passes while the request waits in a partial batch
        fails with DeadlineExceeded at flush time, not a late success."""
        rt = _runtime(cfg, params, max_wait_s=0.4)
        with rt:
            fut = rt.submit(_clouds(1)[0], timeout_s=0.05)  # << max_wait_s
            with pytest.raises(DeadlineExceeded):
                fut.result(timeout=WAIT_S)
        assert rt.metrics.expired == 1
        assert rt.metrics.completed == 0

    def test_restart_after_stop_fails_fast(self, cfg, params):
        rt = _runtime(cfg, params)
        rt.start()
        rt.stop()
        with pytest.raises(RuntimeError, match="restarted"):
            rt.start()

    def test_snapshot_occupancy_excludes_warmup(self, cfg, params):
        rt = _runtime(cfg, params)
        rt.warmup()  # records n_real=0 batches
        futs = [rt.submit(c) for c in _clouds(MAX_BATCH, seed=7)]
        with rt:
            for f in futs:
                f.result(timeout=WAIT_S)
        snap = rt.metrics.snapshot()
        assert snap.mean_occupancy == 1.0  # one full batch; warmup excluded
        assert snap.batches == 1


class TestRuntimeLifecycle:
    def test_stop_drains_admitted_requests(self, cfg, params):
        rt = _runtime(cfg, params, max_wait_s=10.0)  # wait longer than test
        futs = [rt.submit(c) for c in _clouds(3, seed=5)]
        rt.start()
        # wait on the observable hand-off (scheduler drained the admission
        # queue into its pending partial batch), not a wall-clock guess
        wait_until(
            lambda: rt.queue.depth() == 0,
            desc="scheduler to drain the admission queue",
        )
        rt.stop()  # drain=True must flush the pending partial batch
        for f in futs:
            assert f.result(timeout=1).shape == (cfg.n_classes,)
        assert rt.metrics.completed == 3

    def test_threaded_submit_and_metrics_consistency(self, cfg, params):
        rt = _runtime(cfg, params, max_queue=128)
        n_threads, per_thread = 4, 8
        errors = []

        def client(tid):
            try:
                clouds = _clouds(per_thread, sizes=(256, 150), seed=10 + tid)
                outs = [
                    rt.submit(c).result(timeout=WAIT_S) for c in clouds
                ]
                assert all(o.shape == (cfg.n_classes,) for o in outs)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        with rt:
            threads = [
                threading.Thread(target=client, args=(t,)) for t in range(n_threads)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=WAIT_S)
        assert errors == []
        m = rt.metrics
        assert m.completed == n_threads * per_thread
        assert m.submitted == n_threads * per_thread
        snap = m.snapshot()
        assert snap.latency_p95_s >= snap.latency_p50_s >= 0
        assert 0 < snap.mean_occupancy <= 1
        assert sum(b.n_real for b in m.batch_records) == m.completed


class TestCacheIntrospection:
    def test_stats_and_clear(self, cfg):
        clear_cache()
        s0 = cache_stats()
        assert (s0.hits, s0.misses, s0.size) == (0, 0, 0)
        a = get_accelerator(cfg)
        b = get_accelerator(cfg)
        assert a is b
        s1 = cache_stats()
        assert (s1.hits, s1.misses, s1.size) == (1, 1, 1)
        assert s1.keys == ((cfg.name, "none", "auto", "sequential", None),)
        clear_cache()
        assert cache_stats().size == 0
        # fresh instance after clear (old one stays valid for holders)
        c = get_accelerator(cfg)
        assert c is not a

    def test_concurrent_misses_build_one_artifact(self, cfg):
        """The explicit lock closes the lru_cache race: N threads missing on
        the same key construct exactly one accelerator."""
        clear_cache()
        built = []
        orig = accel_mod.PC2IMAccelerator

        class Counting(orig):
            def __init__(self, *a, **kw):
                built.append(1)
                super().__init__(*a, **kw)

        accel_mod.PC2IMAccelerator = Counting
        try:
            with concurrent.futures.ThreadPoolExecutor(max_workers=8) as ex:
                accels = list(ex.map(lambda _: get_accelerator(cfg), range(16)))
        finally:
            accel_mod.PC2IMAccelerator = orig
        assert len(set(map(id, accels))) == 1
        assert sum(built) == 1
        clear_cache()  # drop the Counting-class artifact
