"""Replica pool — one accelerator replica per device GROUP, least-loaded dispatch.

Each `Replica` pins a copy of the model parameters to one carved group of
`jax.devices()` entries (usually of size one — `devices_per_replica`) and
executes micro-batches on its own single worker thread, so B replicas give
B-way compute overlap while every batch still runs on exactly one group.
Batches under a sharded `ExecutionPolicy` run the accelerator's shard_map
artifact across the group's mesh (`_execute_sharded`); everything else —
dispatch, warmup, heartbeat/wedge eviction, chaos injection, retry,
tracing — is group-size-agnostic.  Health is delegated to
`runtime/fault_tolerance.py`:

  * HeartbeatMonitor — a pump thread feeds a no-op beat through each of the
    replica's executor queues every timeout/4 (worker AND feature thread,
    so pipelined batches are covered too); a wedged thread (hung kernel,
    dead device) stops beating and its monitor evicts the replica.  The
    timeout must therefore exceed the worst-case batch latency.
  * StragglerMonitor — per-batch wall time; slow-but-alive replicas are
    recorded (metrics.straggler_events) for the operator, not evicted.

Eviction re-dispatches the replica's outstanding batches to the surviving
replicas, bounded by `max_retries` per batch; a batch that fails everywhere
fails its future with the last error.  Dispatch is least-loaded (smallest
in-flight count among alive replicas) — with shape buckets in play, queue
depth is a better load proxy than round-robin.

Eviction is two-way: `rejoin()` rebuilds an evicted replica in place — a
fresh params copy pinned to its device, fresh stage executors and heartbeat
pumps, every registered warmup batch replayed so each (bucket, policy)
artifact is traced before real traffic lands on it, and (when the runtime
runs a preprocess cache) the hottest cache entries pre-staged as committed
device trees so the new replica's first all-hit batches skip the host
restack.  `add_replica()`/`retire()` grow and shrink the pool the same way;
`serve/autoscaler.py` drives all three from queue depth and evictions.  The
optional `chaos` hook (serve/chaos.py) observes every real batch at
execution start — the deterministic fault-injection point the recovery
tests and the serve_slo benchmark drive.
"""

from __future__ import annotations

import contextlib
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.accelerator import get_accelerator
from repro.core.engine import (
    result_row,
    result_set_row,
    result_stack,
    result_to_host,
)
from repro.launch.mesh import carve_device_groups, make_replica_mesh
from repro.runtime.fault_tolerance import HeartbeatMonitor, StragglerMonitor
from repro.serve.metrics import BatchRecord, ServeMetrics
from repro.serve.queue import try_set_exception, try_set_result
from repro.serve.trace import span


class NoReplicaAvailable(RuntimeError):
    """Every replica is dead (or was already tried for this batch)."""


class _Entry:
    """One in-flight batch on one replica (retry bookkeeping)."""

    def __init__(self, mb, future: Future, attempts: int, tried: frozenset):
        self.mb = mb
        self.future = future
        self.attempts = attempts
        self.tried = tried
        self.seq = -1  # assigned under the pool lock at registration


class Replica:
    """One device-group-pinned executor: params copy + single worker thread.

    The unit of capacity is a device GROUP (usually of size one): sharded-
    policy batches run the accelerator's shard_map artifact over the
    group's 1-D mesh against `mesh_params` (a replicated pin), while
    unsharded batches keep using the primary device's `params` copy —
    both pins coexist so one replica serves both kinds of traffic (the
    replicated pin aliases the primary one for 1-device groups; sharding
    the tensor-mode weights in MEMORY too is a ROADMAP follow-on).

    Batches under a `pipeline="pipelined"` policy additionally use a second
    single-thread executor: the worker thread dispatches the preprocess
    sub-artifact asynchronously and hands completion to the feature thread,
    so while batch k's feature MLPs run, the worker is already preprocessing
    batch k+1 — per-replica stage overlap.  Both executors are constructed
    eagerly (threads spawn on first use), so shutdown/eviction can never
    race a lazy creation; when liveness is enabled, each executor gets its
    own heartbeat pump, so a wedge in EITHER stage evicts the replica.
    """

    def __init__(self, rid: int, device, params, *, on_straggler=None):
        self.id = rid
        # one device OR a device group (mesh-sharded replica): normalized to
        # a tuple, with `device` the group's primary — every single-device
        # path (batch placement, cache staging, repr) keeps using it, so a
        # 1-device group behaves exactly like the classic replica
        self.devices = tuple(device) if isinstance(device, (tuple, list)) else (device,)
        self.device = self.devices[0]
        self.params = jax.device_put(params, self.device)
        # replicated pin over the group's mesh for sharded-policy batches.
        # For a 1-device group the mesh sharding is equivalent to the
        # primary pin, so device_put aliases the copy above (no duplicate)
        self.mesh = make_replica_mesh(self.devices)
        self.mesh_params = jax.device_put(
            self.params,
            jax.sharding.NamedSharding(self.mesh, jax.sharding.PartitionSpec()),
        )
        self.alive = True
        self.retired = False  # scale-down (don't auto-rejoin) vs fault eviction
        self.evicted_t: float | None = None  # when evict() ran (rejoin delay base)
        self.n_batches = 0
        # pre-staged preprocess-cache entries: key -> (id(entry), committed
        # device tree).  Filled at rejoin/scale-up warmup with the cache's
        # hottest entries so the first all-hit batches skip the host restack;
        # the entry id guards against an entry replaced under the same key.
        self.staged: dict[tuple, tuple[int, object]] = {}
        self.inflight: dict[int, _Entry] = {}
        self.straggler = StragglerMonitor(on_straggler=on_straggler)
        self.heartbeat: HeartbeatMonitor | None = None
        self.feature_heartbeat: HeartbeatMonitor | None = None
        self._executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix=f"pc2im-replica-{rid}"
        )
        # constructed eagerly so shutdown()/eviction can never race a lazy
        # creation and leak it.  ThreadPoolExecutor spawns its thread only on
        # first submit, so with liveness DISABLED sequential-only replicas pay
        # nothing; with heartbeats on, the feature pump's beats spawn it (the
        # price of covering a wedge in either stage)
        self._feature_executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix=f"pc2im-replica-{rid}-feat"
        )
        # double-buffer bound on preprocessed-but-unconsumed batches: without
        # it a burst would let the worker race arbitrarily far ahead,
        # materializing every batch's device-resident intermediates at once
        self._handoff_slots = threading.BoundedSemaphore(2)

    def acquire_handoff(self):
        """Block until a staged-batch slot frees (double buffering).

        Applies the same backpressure `two_stage_schedule`'s bounded queue
        gives the local executor: at most two batches may sit preprocessed
        but not yet consumed by the feature thread.  Raises RuntimeError if
        the replica dies while waiting, so a blocked worker task converts to
        a retry instead of hanging.
        """
        while not self._handoff_slots.acquire(timeout=0.1):
            if not self.alive:
                raise RuntimeError(f"replica {self.id} shut down during hand-off wait")

    def release_handoff(self):
        """Free a staged-batch slot (feature stage consumed its input)."""
        self._handoff_slots.release()

    def submit(self, fn, *args) -> Future:
        """Run fn on the replica's worker thread (admission order preserved)."""
        return self._executor.submit(fn, *args)

    def submit_feature(self, fn, *args) -> Future:
        """Run fn on the feature-stage thread (pipelined batches only).

        Single-threaded, so feature stages of consecutive batches stay
        ordered per replica.
        """
        return self._feature_executor.submit(fn, *args)

    def stage_entry(self, entry) -> None:
        """Pre-stage one preprocess-cache entry as a committed device tree.

        The per-row payload is transferred to this replica's device up
        front, so an all-hit batch made of staged entries stacks them
        device-side (`ReplicaPool._staged_stack`) instead of restacking on
        the host and paying the transfer on the serving path.
        """
        self.staged[entry.key] = (
            id(entry),
            jax.device_put(entry.pre, self.device),
        )

    def shutdown(self):
        """Stop both stage executors without waiting.

        In-flight work is abandoned; the pool re-dispatches it elsewhere or
        fails its futures.
        """
        self.alive = False
        if self.heartbeat is not None:
            self.heartbeat.stop()
        if self.feature_heartbeat is not None:
            self.feature_heartbeat.stop()
        self._executor.shutdown(wait=False)
        self._feature_executor.shutdown(wait=False)


class ReplicaPool:
    """Least-loaded dispatch over per-device replicas with health tracking."""

    def __init__(
        self,
        model_cfg,
        params,
        *,
        n_replicas: int | None = None,
        devices=None,
        devices_per_replica: int = 1,
        heartbeat_timeout_s: float | None = None,
        max_retries: int = 2,
        metrics: ServeMetrics | None = None,
        cache=None,
        stage_top_k: int = 8,
        tracer=None,
    ):
        devices = list(devices) if devices is not None else jax.devices()
        # the unit of capacity is a device GROUP: per_replica=1 reproduces
        # the classic per-device carving; > 1 backs each replica with a mesh
        # (leftover devices that don't fill a group are unused)
        self._groups = carve_device_groups(devices, devices_per_replica)
        n = n_replicas if n_replicas is not None else len(self._groups)
        if n < 1:
            raise ValueError("need at least one replica")
        self.model_cfg = model_cfg
        self.max_retries = max_retries
        self.metrics = metrics or ServeMetrics()
        self.cache = cache  # PreprocessCache | None — pre-staged on rejoin
        self.stage_top_k = stage_top_k
        self.tracer = tracer  # Tracer | None — None means tracing is off
        self.chaos = None  # serve/chaos.py injector hook (tests/benchmarks)
        self._params = params  # host reference: rejoin re-pins a fresh copy
        self._devices = devices
        self._heartbeat_timeout_s = heartbeat_timeout_s
        self._warmup_mbs: list = []  # registered warmup batches, replayed on rejoin
        self._lock = threading.Lock()
        self._seq = 0
        # round-robin devices when asked for more replicas than devices
        # (useful on CPU: several logical replicas exercise the dispatch path)
        self.replicas = [self._make_replica(i) for i in range(n)]
        # background cache fill for all-miss batches (thread spawns on first
        # submit, so uncached pools pay nothing); single-threaded, so inserts
        # land in batch-completion order and a later duplicate's
        # execution-time lookup observes them deterministically
        self._insert_executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="pc2im-cache-insert"
        )
        self._pumps: list[threading.Thread] = []
        for rep in self.replicas:
            self._start_liveness(rep)

    def _make_replica(self, rid: int) -> Replica:
        """Construct one fresh Replica for slot `rid` (params re-pinned).

        Shared by the constructor and `rejoin`/`add_replica`: the replica's
        device group follows the slot (round-robin over the carved groups),
        so a rejoined replica lands back on the group its predecessor used —
        and, for sharded policies, on the exact mesh whose artifacts the
        accelerator already compiled (warm re-trace).  Liveness pumps are
        NOT started here — call `_start_liveness` after the replica is
        visible in `self.replicas`.
        """
        return Replica(
            rid,
            self._groups[rid % len(self._groups)],
            self._params,
            # bind the slot id here: StragglerEvent itself carries no replica
            # attribution, and the monitor is per-replica anyway
            on_straggler=lambda ev, rid=rid: self._on_straggler(rid, ev),
        )

    def _on_straggler(self, rid: int, ev) -> None:
        """Per-replica straggler beat: metrics attribution + trace event."""
        self.metrics.record_straggler(ev, replica_id=rid)
        if self.tracer is not None:
            self.tracer.emit(
                "replica.straggler",
                replica_id=rid,
                args={
                    "duration_s": ev.duration_s,
                    "median_s": ev.median_s,
                    "ratio": ev.ratio,
                },
            )

    def _emit(self, name: str, mb, rep_id: int = -1, args: dict | None = None):
        """Emit one batch-scoped trace event (no-op when untraced).

        Warmup batches carry batch_id == -1 and stay invisible to the trace
        stream, matching their exclusion from metrics.
        """
        tr = self.tracer
        if tr is not None and mb.batch_id != -1:
            tr.emit(name, batch_id=mb.batch_id, replica_id=rep_id, args=args)

    def _span(self, name: str, mb, rep: Replica):
        """A stage span of one batch on one replica (see `trace.span`)."""
        return span(name, self.tracer, batch_id=mb.batch_id, replica_id=rep.id)

    def _fetch(self, mb, rep: Replica, out) -> np.ndarray:
        """Wait for a launched program's result, then copy it to the host."""
        with self._span("batch.wait", mb, rep):
            jax.block_until_ready(out)
        with self._span("batch.d2h", mb, rep):
            return np.asarray(out)

    def _start_liveness(self, rep: Replica) -> None:
        """Attach heartbeat monitors + pumps to one replica (when enabled)."""
        if self._heartbeat_timeout_s is None:
            return
        rep.heartbeat = HeartbeatMonitor(
            self._heartbeat_timeout_s,
            on_dead=lambda rid=rep.id: self.evict(rid, reason="heartbeat"),
        ).start()
        rep.feature_heartbeat = HeartbeatMonitor(
            self._heartbeat_timeout_s,
            on_dead=lambda rid=rep.id: self.evict(rid, reason="feature-heartbeat"),
        ).start()
        for tag, submit, monitor in (
            ("", rep.submit, rep.heartbeat),
            ("-feat", rep.submit_feature, rep.feature_heartbeat),
        ):
            pump = threading.Thread(
                target=self._pump, args=(rep, submit, monitor),
                daemon=True, name=f"pc2im-hb-pump-{rep.id}{tag}",
            )
            pump.start()
            self._pumps.append(pump)

    # -- health ---------------------------------------------------------------

    def _pump(self, rep: Replica, submit, monitor):
        """Route beats THROUGH one of the replica's executor queues.

        A wedged thread stops beating, which is exactly the liveness signal
        we want.  Each stage executor gets its own pump + monitor: the
        worker thread never blocks on device work for pipelined batches, so
        a hung feature stage is only observable through the feature
        executor's queue.
        """
        period = monitor.timeout_s / 4
        while rep.alive:
            try:
                submit(monitor.beat)
            except RuntimeError:  # executor shut down under us
                return
            time.sleep(period)

    def alive_replicas(self) -> list[Replica]:
        """Replicas currently considered healthy (dispatch candidates)."""
        with self._lock:
            return [r for r in self.replicas if r.alive]

    def evict(self, rid: int, *, reason: str):
        """Mark a replica dead and re-dispatch its outstanding batches."""
        with self._lock:
            rep = self.replicas[rid]
            if not rep.alive:
                return
            rep.alive = False
            rep.evicted_t = time.monotonic()
            orphans = list(rep.inflight.values())
            rep.inflight.clear()
        self.metrics.record_eviction()
        if self.tracer is not None:
            self.tracer.emit(
                "replica.evicted",
                replica_id=rid,
                args={"reason": reason, "orphans": len(orphans)},
            )
        rep.shutdown()
        for entry in orphans:
            if entry.future.done():
                continue
            self.metrics.record_retry()
            self._emit("batch.retry", entry.mb, rep_id=rid,
                       args={"attempts": entry.attempts + 1, "reason": reason})
            self._dispatch(
                entry.mb, entry.future, entry.attempts + 1,
                entry.tried | {rid},
                error=NoReplicaAvailable(f"replica {rid} evicted ({reason})"),
            )

    def retire(self, rid: int) -> bool:
        """Scale-down eviction: like `evict` but opts out of auto-rejoin.

        The autoscaler retires replicas when the queue runs shallow;
        `retired=True` keeps its rejoin loop from immediately reviving the
        slot (a later scale-up still can, via `rejoin`).  Returns False if
        the replica was already dead.
        """
        with self._lock:
            rep = self.replicas[rid]
            if not rep.alive:
                return False
            rep.retired = True
        self.evict(rid, reason="scale-down")
        return True

    def rejoin(self, rid: int, *, warm: bool = True) -> bool:
        """Re-admit an evicted replica slot with a fresh warm replica.

        The two-way half of eviction: a fresh `Replica` (new params copy on
        the slot's device, new stage executors, new heartbeat pumps)
        replaces the dead one IN PLACE, so in-flight `tried` sets — which
        exclude the slot by id — stay meaningful for batches that failed on
        the predecessor.  With `warm=True` (the default) every registered
        warmup batch is replayed on the new replica before it is marked
        alive for dispatch, so real traffic never pays its compile latency,
        and the preprocess cache's hottest entries are pre-staged on its
        device (`Replica.stage_entry`).  Returns False when the slot is
        still alive (nothing to do).
        """
        with self._lock:
            if self.replicas[rid].alive:
                return False
            rep = self._make_replica(rid)
            # visible to dispatch only after warmup: alive=False gates _pick
            rep.alive = False
            self.replicas[rid] = rep
        try:
            if warm:
                for mb in list(self._warmup_mbs):
                    self._warmup_on(rep, mb)
                self._stage_cache(rep)
        except Exception:
            rep.shutdown()
            raise
        with self._lock:
            rep.alive = True
        self._start_liveness(rep)
        self.metrics.record_rejoin()
        if self.tracer is not None:
            self.tracer.emit(
                "replica.rejoin", replica_id=rid, args={"warm": warm}
            )
        return True

    def add_replica(self, *, warm: bool = True) -> int:
        """Grow the pool by one fresh replica slot; returns its id.

        Scale-up path of the autoscaler once every existing slot is alive.
        The new replica round-robins onto the pool's devices and is warmed
        (and cache-pre-staged) exactly like a rejoin before dispatch sees
        it.
        """
        with self._lock:
            rid = len(self.replicas)
            rep = self._make_replica(rid)
            rep.alive = False  # invisible to _pick until warm
            self.replicas.append(rep)
        try:
            if warm:
                for mb in list(self._warmup_mbs):
                    self._warmup_on(rep, mb)
                self._stage_cache(rep)
        except Exception:
            rep.shutdown()
            raise
        with self._lock:
            rep.alive = True
        self._start_liveness(rep)
        self.metrics.record_rejoin()
        if self.tracer is not None:
            self.tracer.emit(
                "replica.rejoin", replica_id=rid, args={"warm": warm, "grew": True}
            )
        return rid

    def _stage_cache(self, rep: Replica) -> None:
        """Pre-stage the cache's hottest entries on one replica's device.

        Best-effort: a failed transfer only costs the staged fast path, so
        it must never fail a rejoin.
        """
        if self.cache is None:
            return
        try:
            for entry in self.cache.top_entries(self.stage_top_k):
                rep.stage_entry(entry)
        except Exception:  # noqa: BLE001 — staging is an optimization only
            rep.staged.clear()

    def _warmup_on(self, rep: Replica, mb) -> None:
        """Replay one registered warmup batch synchronously on one replica.

        Used by rejoin/add_replica while the replica is still invisible to
        dispatch (alive=False); attempts starts at the retry budget so a
        failure fails THIS future instead of re-dispatching the warmup
        batch to a healthy replica and masking the broken one.
        """
        entry = _Entry(mb, Future(), attempts=self.max_retries, tried=frozenset())
        with self._lock:
            self._seq += 1
            entry.seq = self._seq
            rep.inflight[entry.seq] = entry
        rep.submit(self._execute, rep, entry)
        entry.future.result(timeout=300)

    def _staged_stack(self, rep: Replica, entries, total: int):
        """Device-side restack of an all-hit batch from pre-staged entries.

        Returns the committed device tree when EVERY entry is staged on
        this replica and still current (the recorded entry id must match —
        an entry replaced under the same content address invalidates its
        staged copy); otherwise None, and the caller falls back to the
        host restack + device_put.  Mirrors `result_stack` exactly —
        zeros_like filler rows, then a leaf-wise stack — so the result is
        bitwise-identical to the host path and hits the same executable.
        """
        rows = []
        for e in entries:
            rec = rep.staged.get(e.key)
            if rec is None or rec[0] != id(e):
                return None
            rows.append(rec[1])
        rows.extend([jax.tree.map(jnp.zeros_like, rows[0])] * (total - len(rows)))
        return jax.device_put(
            jax.tree.map(lambda *r: jnp.stack(r), *rows), rep.device
        )

    # -- dispatch -------------------------------------------------------------

    def submit(self, mb) -> Future:
        """Run one MicroBatch somewhere healthy; future yields np logits."""
        future: Future = Future()
        self._dispatch(mb, future, attempts=0, tried=frozenset())
        return future

    def _pick(self, tried: frozenset) -> Replica | None:
        with self._lock:
            candidates = [
                r for r in self.replicas if r.alive and r.id not in tried
            ]
            if not candidates:
                return None
            return min(candidates, key=lambda r: (len(r.inflight), r.id))

    def _dispatch(self, mb, future: Future, attempts: int, tried: frozenset, error=None):
        if attempts > self.max_retries:
            try_set_exception(future, error or NoReplicaAvailable("retry budget exhausted"))
            return
        rep = self._pick(tried)
        if rep is None:
            try_set_exception(
                future, error or NoReplicaAvailable(f"no replica left (tried {sorted(tried)})")
            )
            return
        entry = _Entry(mb, future, attempts, tried)
        with self._lock:
            lost_race = not rep.alive  # evict() won between _pick and here
            if not lost_race:
                self._seq += 1
                entry.seq = self._seq
                rep.inflight[entry.seq] = entry
        if lost_race:
            self._retry(entry, rep.id, NoReplicaAvailable("replica died"))
            return
        self._emit("batch.dispatched", mb, rep_id=rep.id,
                   args={"attempts": attempts})
        try:
            rep.submit(self._execute, rep, entry)
        except RuntimeError as e:  # executor shut down between pick and submit
            with self._lock:
                was_inflight = rep.inflight.pop(entry.seq, None) is not None
            if was_inflight:  # else a concurrent evict() already re-dispatched
                self._retry(entry, rep.id, e)

    def _retry(self, entry: _Entry, rid: int, err: Exception):
        if entry.future.done():
            return
        self.metrics.record_retry()
        self._emit("batch.retry", entry.mb, rep_id=rid,
                   args={"attempts": entry.attempts + 1, "reason": repr(err)})
        self._dispatch(entry.mb, entry.future, entry.attempts + 1,
                       entry.tried | {rid}, error=err)

    def _execute(self, rep: Replica, entry: _Entry):
        if entry.future.done():  # e.g. already re-dispatched after eviction
            with self._lock:
                rep.inflight.pop(entry.seq, None)
            return
        mb = entry.mb
        if self.chaos is not None and mb.n_real > 0:
            # deterministic fault-injection point: every REAL batch passes
            # here on its replica's worker thread before either execution
            # path (warmup batches are invisible to the injector).  A kill
            # fault evicts the replica — eviction re-dispatches this entry,
            # so the raise below must NOT retry it again (was_inflight)
            try:
                self.chaos.on_batch(self, rep, mb)
            except Exception as e:  # noqa: BLE001 — injected fault
                with self._lock:
                    was_inflight = rep.inflight.pop(entry.seq, None) is not None
                if was_inflight:
                    self._retry(entry, rep.id, e)
                return
        if getattr(mb.policy, "sharding", None) is not None:
            self._execute_sharded(rep, entry)
            return
        if getattr(mb.policy, "pipeline", "sequential") == "pipelined":
            self._execute_pipelined(rep, entry)
            return
        try:
            accel = get_accelerator(self.model_cfg, mb.policy)
            rep.straggler.step_start()
            with self._span("batch.h2d", mb, rep):
                batch = jax.device_put(jnp.asarray(mb.batch), rep.device)
            if mb.cache is not None:
                logits, skipped = self._run_cached(accel, rep, mb, batch)
            else:
                with self._span("batch.execute", mb, rep):
                    with self._span("batch.launch", mb, rep):
                        out = accel.infer(rep.params, batch)
                    logits = self._fetch(mb, rep, out)
                skipped = False
            dt = rep.straggler.step_end(rep.n_batches)
            if rep.heartbeat is not None:
                rep.heartbeat.beat()
            with self._span("batch.complete", mb, rep):
                self._record_success(rep, entry, logits, dt, preprocess_skipped=skipped)
        except Exception as e:  # noqa: BLE001 — any device/kernel failure
            # retry only if the entry was still ours: a concurrent evict()
            # already cleared inflight AND re-dispatched it — retrying here
            # too would run the batch twice
            with self._lock:
                was_inflight = rep.inflight.pop(entry.seq, None) is not None
            if was_inflight:
                self._retry(entry, rep.id, e)

    def _execute_sharded(self, rep: Replica, entry: _Entry):
        """Mesh-sharded execution of one batch over the replica's device group.

        Routes through the accelerator's `mesh_artifacts` for this group —
        a 1-device group gets a degenerate mesh, so the policy's semantics
        never depend on the pool's carving.  Straggler tracking, heartbeat
        beats, retry-on-failure and trace spans behave exactly like the
        sequential path (chaos already ran in `_execute`).  The preprocess
        cache deliberately does not compose with sharded policies yet —
        the scheduler never attaches it to a sharded batch (cached rows
        are single-device host trees, not mesh-laid-out ones; see ROADMAP).
        """
        mb = entry.mb
        try:
            accel = get_accelerator(self.model_cfg, mb.policy)
            arts = accel.mesh_artifacts(rep.devices)
            rep.straggler.step_start()
            with self._span("batch.execute", mb, rep):
                with self._span("batch.h2d", mb, rep):
                    points = jnp.asarray(mb.batch)
                with self._span("batch.launch", mb, rep):
                    out = arts.infer(rep.mesh_params, points)
                logits = self._fetch(mb, rep, out)
            dt = rep.straggler.step_end(rep.n_batches)
            if rep.heartbeat is not None:
                rep.heartbeat.beat()
            with self._span("batch.complete", mb, rep):
                self._record_success(rep, entry, logits, dt)
        except Exception as e:  # noqa: BLE001 — any device/kernel failure
            with self._lock:
                was_inflight = rep.inflight.pop(entry.seq, None) is not None
            if was_inflight:  # else a concurrent evict() already re-dispatched
                self._retry(entry, rep.id, e)

    # -- preprocess-cache execution -------------------------------------------

    def _resolve_entries(self, mb) -> tuple:
        """Authoritative, counted cache lookups for one batch at execution time.

        The scheduler peeked at assembly time (to substitute canonical rows);
        by the time the batch EXECUTES, every earlier batch on this replica
        has finished inserting, so a request that peek-missed while its
        duplicate's batch was still in flight can upgrade to a hit here —
        under a backlogged cyclic trace this is where most hits come from.
        A late hit is accepted only when the assembled batch row is
        bitwise-equal to the entry's canonical row (always true for exact
        duplicates; a sub-step-noise near-duplicate whose row was NOT
        canonicalized at assembly keeps the miss path, preserving parity).
        Returns one CacheEntry-or-None per request; exactly one counted
        lookup per addressable request.
        """
        entries = []
        hits = misses = 0
        for i, req in enumerate(mb.requests):
            ent = None
            if req.cache_key is not None:
                ent = mb.cache.lookup(req.cache_key)
                if ent is not None and not np.array_equal(mb.batch[i], ent.row):
                    ent = None
                if ent is not None:
                    hits += 1
                else:
                    misses += 1
            entries.append(ent)
        # one metrics-lock round trip per outcome, not per request — the
        # metrics lock is shared with the scheduler's hot path
        if hits:
            self.metrics.record_cache_lookup(True, hits)
        if misses:
            self.metrics.record_cache_lookup(False, misses)
        if self.tracer is not None and mb.batch_id != -1:
            for req, ent in zip(mb.requests, entries):
                if req.trace_id is not None and req.cache_key is not None:
                    self.tracer.emit(
                        "request.cache_lookup",
                        trace_id=req.trace_id,
                        batch_id=mb.batch_id,
                        slo=req.slo.name,
                        args={"hit": ent is not None},
                    )
        return tuple(entries)

    def _run_cached(self, accel, rep, mb, batch):
        """Cache-aware execution of one batch; returns (logits, skipped).

        All-hit: the preprocess stage is skipped outright — the per-row
        cached neighborhoods are restacked (zero filler rows matching the
        zero filler batch rows) and fed straight to `feature_from_cached`.
        All-miss: `infer_with_preprocess` — ONE dispatch at fused-path cost
        whose second output feeds the background cache fill, so the
        0%-duplicate workload pays nothing over the uncached path.
        Mixed: the batch runs `preprocess_stage` (the staged composition is
        bitwise-equal to the fused `infer`, pinned by
        tests/test_pipelined_accelerator.py, so miss parity is preserved),
        hit rows are spliced in on the host, and miss rows populate the
        cache before the feature stage runs.
        """
        if mb.n_real == 0:
            # warmup batch: trace EVERY artifact a cached batch can touch so
            # no variant compiles mid-traffic (a multi-hundred-ms stall)
            fused, _pre = accel.infer_with_preprocess(rep.params, batch)
            pre = accel.preprocess_stage(batch)
            logits = np.asarray(
                jax.block_until_ready(accel.feature_stage(rep.params, batch, pre))
            )
            jax.block_until_ready(fused)
            return logits, False
        with self._span("batch.cache", mb, rep) as end:
            entries = self._resolve_entries(mb)
            n_hits = sum(1 for e in entries if e is not None)
            end["hits"] = n_hits
            all_hit = n_hits == mb.n_real
            if all_hit:
                end["skip"] = True
                # device_put: the feature artifact must only ever see COMMITTED
                # device trees — a host-numpy variant would compile a second
                # executable for the same shapes (a one-off multi-hundred-ms
                # stall mid-traffic).  Pre-staged entries (warm rejoin) stack
                # device-side and skip the host restack + transfer entirely
                pre = self._staged_stack(rep, entries, mb.batch.shape[0])
                if pre is None:
                    pre = jax.device_put(
                        result_stack([e.pre for e in entries], total=mb.batch.shape[0]),
                        rep.device,
                    )
        if all_hit:
            with self._span("batch.feature", mb, rep):
                with self._span("batch.launch", mb, rep):
                    out = accel.feature_from_cached(rep.params, batch, pre)
                logits = self._fetch(mb, rep, out)
            return logits, True
        if n_hits == 0:
            with self._span("batch.execute", mb, rep):
                with self._span("batch.launch", mb, rep):
                    logits_dev, pre = accel.infer_with_preprocess(rep.params, batch)
                logits = self._fetch(mb, rep, logits_dev)
            self._insert_executor.submit(self._insert_misses, mb, pre, entries)
            return logits, False
        # mixed: block on the preprocess result explicitly (result_to_host is
        # a no-op copy on the already-host tree inside _cached_splice), so the
        # preprocess span measures the stage compute and the splice span only
        # the host row surgery + cache fill
        with self._span("batch.preprocess", mb, rep):
            pre_host = result_to_host(accel.preprocess_stage(batch))
        with self._span("batch.splice", mb, rep):
            pre = jax.device_put(
                self._cached_splice(mb, pre_host, entries),
                rep.device,
            )
        with self._span("batch.feature", mb, rep):
            with self._span("batch.launch", mb, rep):
                out = accel.feature_stage(rep.params, batch, pre)
            logits = self._fetch(mb, rep, out)
        return logits, False

    def _splice_or_insert(self, rep, mb, pre, entries):
        """Route one non-all-hit pipelined cache batch's preprocess output.

        Mixed (some hits): the host splice path — hit rows must replace the
        freshly computed ones before the feature stage consumes them, and
        the spliced tree goes back to the device committed (same executable
        as the miss path, see `_run_cached`).  All-miss: the device tree is
        returned UNTOUCHED (the feature stage runs exactly the uncached
        staged composition, no host round trip on the critical path) and
        miss insertion happens on the pool's background insert thread —
        cache fill is bookkeeping, not part of the response, so it must not
        tax the 0%-duplicate workload.
        """
        if any(e is not None for e in entries):
            return jax.device_put(
                self._cached_splice(mb, pre, entries), rep.device
            )
        self._insert_executor.submit(self._insert_misses, mb, pre, entries)
        return pre

    def _cached_splice(self, mb, pre, entries):
        """Host splice of hits + cache insertion of misses on one batch.

        `pre` is the batched `preprocess_stage` output; `entries` the
        execution-time resolved CacheEntry-or-None per request.  Returns the
        host result tree the feature stage should consume: miss rows exactly
        as the stage computed them (the round trip through the host is
        bitwise-lossless), hit rows replaced by their cached payloads
        (whose canonical clouds already sit in the batch rows).  Miss rows
        with a content address populate the cache before the feature stage
        runs, so a concurrent duplicate can hit as early as possible.
        """
        pre = result_to_host(pre)
        for i, ent in enumerate(entries):
            if ent is not None:
                result_set_row(pre, i, ent.pre)
        self._insert_misses(mb, pre, entries)
        return pre

    def _insert_misses(self, mb, pre, entries):
        """Populate the cache with one batch's miss rows (best effort).

        `pre` may be a device tree (async all-miss path) or the already
        host-resident splice output; `result_to_host` is a no-op copy for
        the latter.  Failures are swallowed: the response already shipped
        (or ships independently), and a lost fill only costs a future hit.
        """
        try:
            pre = result_to_host(pre)
            for i, req in enumerate(mb.requests):
                hit = i < len(entries) and entries[i] is not None
                if not hit and req.cache_key is not None:
                    mb.cache.insert(req.cache_key, mb.batch[i], result_row(pre, i))
        except Exception:  # noqa: BLE001 — cache fill must never fail a batch
            pass

    def _record_success(
        self,
        rep: Replica,
        entry: _Entry,
        logits,
        dt: float,
        *,
        preprocess_skipped: bool = False,
    ):
        """Success bookkeeping shared by the sequential and pipelined paths.

        exactly-one-winner: an evicted-but-still-running replica can race
        its batch's re-dispatched copy to this future — only the completion
        that lands records the batch, so metrics count each logical
        micro-batch once.  n_batches is under the pool lock because the
        worker AND feature threads both count here under mixed schedules.
        """
        mb = entry.mb
        with self._lock:
            rep.n_batches += 1
            rep.inflight.pop(entry.seq, None)
        if try_set_result(entry.future, logits):
            self.metrics.record_batch(BatchRecord(
                bucket=mb.bucket,
                policy_key=(
                    mb.policy.quant,
                    mb.policy.backend,
                    mb.policy.pipeline,
                    getattr(mb.policy, "sharding", None),
                ),
                n_real=mb.n_real,
                batch_size=mb.batch.shape[0],
                replica_id=rep.id,
                duration_s=dt,
                preprocess_skipped=preprocess_skipped,
                batch_id=getattr(mb, "batch_id", -1),
            ))

    def _execute_pipelined(self, rep: Replica, entry: _Entry):
        """Two-stage execution of one batch on the replica.

        Preprocess runs on the worker thread (async dispatch, never blocked
        on), the feature MLPs on the feature thread.
        The worker returns as soon as the feature stage is handed off, so it
        starts preprocessing the NEXT queued batch while this one's feature
        MLPs run — the Mesorasi-style overlap, per replica.  Liveness: each
        stage executor has its own heartbeat pump (when enabled), so a
        wedged feature thread stops the feature beats and the replica is
        evicted, re-dispatching its in-flight batches — the same coverage
        the sequential path gets from the worker pump.  Straggler tracking
        is skipped for pipelined batches (overlapping spans would corrupt
        its single-slot timer); BatchRecord.duration_s is measured directly.
        """
        mb = entry.mb
        try:
            accel = get_accelerator(self.model_cfg, mb.policy)
            rep.acquire_handoff()  # double-buffer bound (released by feature stage)
            try:
                with self._span("batch.h2d", mb, rep):
                    batch = jax.device_put(jnp.asarray(mb.batch), rep.device)
                skipped = False
                if mb.cache is not None:
                    # resolved on the worker thread: the pipelined worker runs
                    # one batch ahead of the feature thread, so late hits from
                    # the immediately preceding batch's insert may still miss
                    # — correctness is unaffected, only the skip opportunity
                    with self._span("batch.cache", mb, rep) as end:
                        entries = self._resolve_entries(mb)
                        skipped = mb.n_real > 0 and all(e is not None for e in entries)
                        if skipped:
                            end["skip"] = True
                            # cache skip composes with the pipeline: the worker
                            # hands the restacked payload straight to the feature
                            # thread — no preprocess dispatch at all for this
                            # batch (device_put: committed, same executable as
                            # miss batches; pre-staged entries stack
                            # device-side, no host restack)
                            pre = self._staged_stack(rep, entries, mb.batch.shape[0])
                            if pre is None:
                                pre = jax.device_put(
                                    result_stack(
                                        [e.pre for e in entries], total=mb.batch.shape[0]
                                    ),
                                    rep.device,
                                )
                else:
                    entries = ()
                if not skipped:
                    # async — the span measures the dispatch only; the stage's
                    # device time is charged to the feature span through the
                    # data dependency (block_until_ready)
                    with self._span("batch.preprocess", mb, rep):
                        with self._span("batch.launch", mb, rep):
                            pre = accel.preprocess_stage(batch)  # hand off, don't block
                if rep.heartbeat is not None:
                    rep.heartbeat.beat()
                rep.submit_feature(
                    self._finish_pipelined, rep, entry, accel, batch, pre, skipped,
                    entries,
                )
            except Exception:
                rep.release_handoff()  # the feature stage will never run for us
                raise
        except Exception as e:  # noqa: BLE001 — dispatch/executor failure
            with self._lock:
                was_inflight = rep.inflight.pop(entry.seq, None) is not None
            if was_inflight:  # else a concurrent evict() already re-dispatched
                self._retry(entry, rep.id, e)

    def _finish_pipelined(
        self,
        rep: Replica,
        entry: _Entry,
        accel,
        batch,
        pre,
        skipped: bool = False,
        entries: tuple = (),
    ):
        try:
            if entry.future.done():  # re-dispatched after eviction while queued
                with self._lock:
                    rep.inflight.pop(entry.seq, None)
                return
            # timed from HERE, not worker dispatch: queue wait behind earlier
            # batches' feature stages is pipeline overlap, not this batch's
            # cost (block_until_ready still charges any unfinished preprocess
            # through the data dependency)
            t0 = time.monotonic()
            try:
                mb = entry.mb
                if skipped:
                    feature = accel.feature_from_cached
                else:
                    if mb.cache is not None:
                        # mixed cache batch: host splice on the feature
                        # thread (blocks on the preprocess result through
                        # the transfer, same data dependency); all-miss
                        # batches keep the device tree + async insert
                        mixed = any(e is not None for e in entries)
                        with (self._span("batch.splice", mb, rep) if mixed
                              else contextlib.nullcontext()):
                            pre = self._splice_or_insert(rep, mb, pre, entries)
                    feature = accel.feature_stage
                with self._span("batch.feature", mb, rep):
                    with self._span("batch.launch", mb, rep):
                        out = feature(rep.params, batch, pre)
                    logits = self._fetch(mb, rep, out)
                dt = time.monotonic() - t0
                if rep.feature_heartbeat is not None:
                    rep.feature_heartbeat.beat()
                with self._span("batch.complete", mb, rep):
                    self._record_success(
                        rep, entry, logits, dt, preprocess_skipped=skipped
                    )
            except Exception as e:  # noqa: BLE001 — any device/kernel failure
                with self._lock:
                    was_inflight = rep.inflight.pop(entry.seq, None) is not None
                if was_inflight:  # else evict() already re-dispatched it
                    self._retry(entry, rep.id, e)
        finally:
            rep.release_handoff()

    # -- lifecycle ------------------------------------------------------------

    def warmup(self, mb):
        """Compile + run one batch synchronously on EVERY alive replica.

        The runtime uses this to pre-trace each (bucket, policy) artifact —
        for pipelined policies this drives the two-stage path, so BOTH
        sub-artifacts are traced before real traffic arrives.  Each distinct
        (bucket, policy) batch is also REGISTERED: rejoin/add_replica replay
        the registered set on a fresh replica so it joins warm.
        """
        with self._lock:
            for i, m in enumerate(self._warmup_mbs):
                if m.bucket == mb.bucket and m.policy == mb.policy:
                    # same key, new static shape (a live max_batch
                    # reconfiguration): rejoins must replay the CURRENT
                    # shape, so the registration is replaced, not dropped
                    if m.batch.shape != mb.batch.shape:
                        self._warmup_mbs[i] = mb
                    break
            else:
                self._warmup_mbs.append(mb)
        futs = []
        for rep in self.alive_replicas():
            entry = _Entry(mb, Future(), attempts=self.max_retries, tried=frozenset())
            with self._lock:
                self._seq += 1
                entry.seq = self._seq
                rep.inflight[entry.seq] = entry
            rep.submit(self._execute, rep, entry)
            futs.append(entry.future)
        for f in futs:
            f.result(timeout=300)

    def shutdown(self):
        """Stop every replica (abandoning in-flight batches and cache fills)."""
        for rep in self.replicas:
            rep.shutdown()
        self._insert_executor.shutdown(wait=False)
