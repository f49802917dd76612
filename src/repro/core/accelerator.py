"""PC2IMAccelerator — one (config, policy) pair -> compiled whole-pipeline artifacts.

The paper's accelerator is ONE device: the CIM preprocessing dataflow
(MSP -> L1 FPS -> lattice query) and the SC-CIM feature engine (quantized
per-point MLPs) are co-scheduled halves of the same chip.  This module is
the software image of that: a `PC2IMAccelerator` owns

  * the per-SA-stage `PreprocessEngine`s (batch x MSP tiles folded into one
    kernel grid, backend chosen by the policy), and
  * the policy-driven feature path (every `nn.linear` under the same
    `ExecutionPolicy` — float or SC W16A16/W8A8 through the kernel registry),

and exposes cached, jit-compiled `forward` / `infer` / `loss` artifacts:

    accel = get_accelerator(get_config("pointnet2-cls"),
                            ExecutionPolicy(quant="sc_w16a16"))
    params = accel.init(jax.random.PRNGKey(0))
    logits = accel.infer(params, points)        # (B, N, 3+F) -> (B, C)
    loss, metrics = accel.loss(params, points, labels)

Because `ExecutionPolicy` and `PointNet2Config` are frozen/hashable, the
accelerator cache gives exactly one compiled artifact per distinct
(config, policy) — concurrent serving threads with different policies get
different accelerators and can never interfere (the failure mode of the
removed thread-local `nn.quant_mode`).
"""

from __future__ import annotations

import dataclasses
import threading

import jax

from repro.core.policy import ExecutionPolicy, resolve_policy
from repro.launch.mesh import make_replica_mesh
from repro.models import pointnet2 as PN
from repro.parallel.pipeline import two_stage_schedule
from repro.sharding.hints import REPLICA_AXIS
from repro.sharding.policy import replica_specs


class PC2IMAccelerator:
    """Compiled PC2IM pipeline for one (PointNet2Config, ExecutionPolicy).

    Attributes:
        config  : the model/architecture description (WHAT to run).
        policy  : the execution description (HOW to run) — quant mode,
                  kernel backend, interpret flag.
        engines : per-SA-stage PreprocessEngines, stage i consuming stage
                  i-1's centroid count (shared with the forward trace via
                  the global engine cache, so nothing compiles twice).
    """

    def __init__(self, config: PN.PointNet2Config, policy: ExecutionPolicy | None = None):
        self.config = config
        # resolve once: backend=None picks up the config's pinned backend for
        # BOTH halves (engines and feature path) before anything is traced
        self.policy = resolve_policy(config, policy)

        engines = []
        n = config.n_points
        for sa in config.sa:
            engines.append(PN.stage_engine(config, sa, n, self.policy))
            n = sa.n_centroids
        self.engines = tuple(engines)

        cfg, pol = self.config, self.policy
        # jit closes over the static (config, policy) pair: one artifact per
        # accelerator, retraced only per input shape/dtype.
        self._forward = jax.jit(
            lambda params, points: PN.forward(params, cfg, points, policy=pol)
        )
        self._loss = jax.jit(
            lambda params, points, labels: PN.loss_fn(
                params, cfg, points, labels, policy=pol
            )
        )
        # the fused forward IS feature_stage(preprocess_stage(...)) — these
        # sub-artifacts run the same code behind separate jit boundaries, so
        # a pipelined schedule can overlap micro-batch k+1's preprocessing
        # with micro-batch k's feature MLPs without changing one output bit
        self._preprocess_stage = jax.jit(
            lambda points: PN.preprocess_stage(cfg, points, policy=pol)
        )
        self._feature_stage = jax.jit(
            lambda params, points, pre: PN.feature_stage(
                params, cfg, points, pre, policy=pol
            )
        )

        # fused forward that ALSO materializes the preprocess intermediates:
        # one dispatch at fused-path cost, with the neighborhoods coming out
        # as a second output.  `forward` IS feature_stage(preprocess_stage),
        # so the logits here are the same composition with an extra output —
        # the serving cache's all-miss path uses this to fill the cache
        # without paying a separate preprocess dispatch.
        def _fused_with_pre(params, points):
            pre = PN.preprocess_stage(cfg, points, policy=pol)
            return PN.feature_stage(params, cfg, points, pre, policy=pol), pre

        self._infer_with_pre = jax.jit(_fused_with_pre)
        # PipelinedExecutor cache for infer_pipelined (keyed by devices/depth)
        self._executors: dict = {}
        self._executors_lock = threading.Lock()
        # MeshArtifacts cache for sharded policies (keyed by device group):
        # the global accelerator cache keys on (config, policy) only, but a
        # sharded artifact is additionally pinned to ONE replica's mesh —
        # same lazy per-devices pattern as the PipelinedExecutor cache above
        self._mesh_artifacts: dict = {}
        self._mesh_lock = threading.Lock()

    # -- artifacts -----------------------------------------------------------

    def init(self, key):
        """Fresh parameters for this accelerator's config."""
        return PN.init_params(key, self.config)

    def forward(self, params, points: jax.Array) -> jax.Array:
        """jit-compiled batched forward: (B, N, 3+F) -> logits."""
        return self._forward(params, points)

    def infer(self, params, points: jax.Array) -> jax.Array:
        """Inference entry point — same compiled artifact as `forward`.

        Serving call-sites read better as `accel.infer`.
        """
        return self._forward(params, points)

    @property
    def infer_program(self):
        """The jitted artifact `infer` and `forward` run.

        Its `.lower(params, points).compile()` is the program that serves,
        with the instruction names a profiler trace shows; a `jax.jit` of
        `infer` would compile another module, under other names.
        """
        return self._forward

    def loss(self, params, points: jax.Array, labels: jax.Array):
        """jit-compiled (loss, metrics) under this accelerator's policy."""
        return self._loss(params, points, labels)

    def loss_fn(self, params, points: jax.Array, labels: jax.Array):
        """Un-jitted loss for jax.grad / custom training loops.

        Still pinned to this accelerator's policy.
        """
        return PN.loss_fn(params, self.config, points, labels, policy=self.policy)

    # -- staged sub-artifacts (the pipelined execution path) -----------------

    def preprocess_stage(self, points: jax.Array) -> tuple:
        """Params-free preprocessing sub-artifact, one PreprocessResult per SA stage.

        Chains MSP partition + FPS + neighbour query stage after stage.
        This is the half of `infer` that never reads the model parameters —
        only coordinates — which is what makes it safe to run for micro-batch
        k+1 while micro-batch k is still inside `feature_stage`.
        """
        return self._preprocess_stage(points)

    def feature_stage(self, params, points: jax.Array, preproc: tuple) -> jax.Array:
        """Feature sub-artifact: SC-CIM per-point MLPs + aggregation.

        Consumes the neighborhoods `preprocess_stage` computed.
        `feature_stage(params, pts, preprocess_stage(pts))` is bitwise-equal
        to `infer(params, pts)` (pinned by tests/test_pipelined_accelerator.py).
        """
        return self._feature_stage(params, points, preproc)

    def feature_from_cached(self, params, points: jax.Array, preproc) -> jax.Array:
        """Feature stage over CACHE-RESTACKED neighborhoods — the hit fast path.

        Entry point for the cross-request preprocess cache
        (serve/preprocess_cache.py): `preproc` is a host-resident result
        tree reassembled from per-row cache entries
        (`core.engine.result_stack`) instead of a live `preprocess_stage`
        output.  It deliberately runs the SAME compiled artifact as
        `feature_stage` — a cache-hit batch whose rows are the cached
        canonical clouds therefore produces logits bitwise-equal to an
        uncached recomputation of those clouds, with the whole preprocess
        half of the chip skipped.
        """
        return self._feature_stage(params, points, preproc)

    def infer_with_preprocess(self, params, points: jax.Array) -> tuple:
        """Fused forward returning (logits, preprocess payload) in one dispatch.

        The cross-request preprocess cache's all-miss path: the batch pays
        exactly one artifact call (same composition as `infer`, so the
        logits are bitwise-equal — pinned by tests/test_preprocess_cache.py)
        while the preprocess intermediates come out as a second output for
        the cache-fill thread to store.
        """
        return self._infer_with_pre(params, points)

    def infer_pipelined(self, params, batches, *, devices=None, depth: int = 2) -> list:
        """Run a stream of micro-batches through the two-stage pipeline.

        Convenience wrapper over `PipelinedExecutor`: batch k+1's
        preprocessing overlaps batch k's feature MLPs.  Returns one logits
        array per input batch, in order, each bitwise-equal to
        `infer(params, batch)`.  The executor is cached per (devices,
        depth), so repeated calls on a multi-device host reuse the placed
        parameters instead of re-transferring them every call.
        """
        key = (tuple(devices) if devices is not None else None, depth)
        with self._executors_lock:
            ex = self._executors.get(key)
            if ex is None:
                ex = self._executors[key] = PipelinedExecutor(
                    self, devices=devices, depth=depth
                )
        return ex.run(params, batches)

    def mesh_artifacts(self, devices) -> "MeshArtifacts":
        """Sharded infer/forward artifacts over one replica's device group.

        Requires a policy with `sharding` set (the mode picks the
        shard_map body — see MeshArtifacts).  Artifacts are built lazily
        and cached per device tuple, so a pool of mesh replicas sharing one
        accelerator compiles each group's artifact exactly once and a
        rejoined replica on the same group re-traces nothing.
        """
        if self.policy.sharding is None:
            raise ValueError(
                "mesh_artifacts needs a policy with sharding set; "
                "use infer/forward for unsharded execution"
            )
        key = tuple(devices)
        with self._mesh_lock:
            arts = self._mesh_artifacts.get(key)
            if arts is None:
                arts = self._mesh_artifacts[key] = MeshArtifacts(self, key)
        return arts

    def __repr__(self) -> str:
        return (
            f"PC2IMAccelerator({self.config.name}, quant={self.policy.quant!r}, "
            f"backend={self.policy.backend!r}, stages={len(self.engines)})"
        )


class PipelinedExecutor:
    """Double-buffered two-stage executor over one accelerator's sub-artifacts.

    Streams micro-batches through `preprocess_stage` -> `feature_stage` so
    batch k+1's preprocessing (FPS / lattice kernels — the paper's APD-CIM
    and Ping-Pong-MAX CAM half) overlaps batch k's SC-CIM feature MLPs,
    mirroring how the hardware's CAM updates temporary distances while
    search proceeds:

        ex = PipelinedExecutor(get_accelerator(cfg, policy))
        logits = ex.run(params, batches)     # list, one per batch, in order

    On ONE device the overlap comes from jax's asynchronous dispatch: the
    producer thread enqueues preprocessing without ever calling
    `block_until_ready`, so the device schedules it behind/alongside the
    feature computation already in flight.  With >= 2 devices the stages are
    pinned to different devices (preprocess on `devices[0]`, features on
    `devices[1]`, parameters resident there) and the hand-off transfers the
    intermediate neighborhoods — true two-stage pipeline parallelism via
    `parallel.pipeline.two_stage_schedule`.

    Results are bitwise-equal to sequential `infer` calls: both paths run
    the same compiled sub-artifact composition (pinned test).
    """

    def __init__(self, accel: PC2IMAccelerator, *, devices=None, depth: int = 2):
        self.accel = accel
        self.devices = tuple(devices) if devices is not None else tuple(jax.devices())
        self.depth = depth
        # last (params, placed-on-feature-device copy) pair, reused across
        # run() calls so a serving loop doesn't re-transfer the weights every
        # stream (identity check: params pytrees are treated as immutable).
        # NOTE the latest generation stays referenced until the next swap or
        # clear_cache() — the same lifetime replica params already have in
        # serve/dispatch.py, where each Replica pins a device copy for good
        self._placed: tuple = (None, None)

    def _params_on(self, params, device):
        cached_key, cached_placed = self._placed
        if cached_key is params:
            return cached_placed
        # return the LOCAL, never re-read self._placed: a concurrent run()
        # with different params may overwrite the cache between assignment
        # and return, and this stream must keep ITS weights either way
        placed = jax.device_put(params, device)
        self._placed = (params, placed)
        return placed

    def run(self, params, batches) -> list:
        """Execute every (B, N, 3+F) batch; returns per-batch logits in order.

        The returned arrays are still asynchronous jax values — block (or
        `np.asarray` them) when the wall-clock matters.
        """
        accel = self.accel
        if len(self.devices) >= 2:
            dev_pre, dev_feat = self.devices[0], self.devices[1]
            params_feat = self._params_on(params, dev_feat)

            def stage_a(batch):
                batch = jax.device_put(batch, dev_pre)
                return batch, accel.preprocess_stage(batch)

            def stage_b(handoff):
                batch, pre = jax.device_put(handoff, dev_feat)
                return accel.feature_stage(params_feat, batch, pre)

        else:

            def stage_a(batch):
                # async dispatch: enqueue and hand off, never block
                return batch, accel.preprocess_stage(batch)

            def stage_b(handoff):
                batch, pre = handoff
                return accel.feature_stage(params, batch, pre)

        return two_stage_schedule(stage_a, stage_b, batches, depth=self.depth)


class MeshArtifacts:
    """Sharded whole-pipeline artifact of one accelerator over one device group.

    The serving analog of the paper's split-concatenate engine spanning
    subarrays: one replica owns a 1-D `Mesh` (launch.mesh.make_replica_mesh)
    and the fused preprocess+feature composition runs under `shard_map`
    with specs resolved by `sharding.policy.replica_specs`:

      * "batch"  — every stage runs on its local batch rows; the only
        cross-device term is the exact pmax globalizing the activation
        quant scale (core.quant), so each row's math is untouched.
      * "tensor" — preprocess runs batch-sharded, then the neighborhoods
        are all-gathered and the feature MLPs column-split every weight
        across the group, concatenating partial products (nn.linear's
        tensor path); each device finally returns its row slice of the
        replicated logits.

    Both modes are bitwise-equal to the accelerator's single-device
    `infer` on the same batch (pinned by tests/test_sharded_replica.py;
    on the CPU backend, whose dot bits depend on the dot's width, fp32
    tensor mode is compared with a forward that multiplies in the same
    column blocks; on the chip by `chip_smoke.py --chips 4`).
    `check_vma=False` matches the repo's shard_map precedent
    (parallel/pipeline.py) — the tensor mode's gathered intermediates are
    replicated values the varying-axes checker can't see through.
    """

    def __init__(self, accel: PC2IMAccelerator, devices):
        self.mesh = make_replica_mesh(devices)
        cfg, pol = accel.config, accel.policy
        mode = pol.sharding
        p_params, p_points, p_logits = replica_specs(mode)

        def mapped(params, points):
            pre = PN.preprocess_stage(cfg, points, policy=pol)
            if mode == "batch":
                return PN.feature_stage(params, cfg, points, pre, policy=pol)
            # tensor: globalize the batch-sharded neighborhoods, run the
            # feature stage replicated (its linears column-split across the
            # group internally), then keep only this device's rows so the
            # out_spec can reassemble the global batch
            pts = jax.lax.all_gather(points, REPLICA_AXIS, axis=0, tiled=True)
            pre = jax.tree.map(
                lambda t: jax.lax.all_gather(t, REPLICA_AXIS, axis=0, tiled=True),
                pre,
            )
            logits = PN.feature_stage(params, cfg, pts, pre, policy=pol)
            idx = jax.lax.axis_index(REPLICA_AXIS)
            rows = points.shape[0]
            return jax.lax.dynamic_slice_in_dim(logits, idx * rows, rows, axis=0)

        self._infer = jax.jit(
            jax.shard_map(
                mapped,
                mesh=self.mesh,
                in_specs=(p_params, p_points),
                out_specs=p_logits,
                check_vma=False,
            )
        )

    def infer(self, params, points: jax.Array) -> jax.Array:
        """Sharded batched forward: (B, N, 3+F) -> logits, B % mesh.size == 0."""
        if points.shape[0] % self.mesh.size != 0:
            raise ValueError(
                f"batch dim {points.shape[0]} must divide over the replica "
                f"mesh of {self.mesh.size} device(s)"
            )
        return self._infer(params, points)

    def forward(self, params, points: jax.Array) -> jax.Array:
        """Alias of `infer` — same compiled artifact, training-style name."""
        return self.infer(params, points)


@dataclasses.dataclass(frozen=True)
class CacheStats:
    """Snapshot of the accelerator cache (see `cache_stats`).

    hits/misses count `get_accelerator` calls; size is the number of live
    artifacts; keys names each artifact as (config.name, quant, backend,
    pipeline, sharding) so tests and the serving runtime can assert
    one-artifact-per-(config, policy) — pipelined vs sequential and sharded
    vs unsharded traffic all resolve to DIFFERENT keys — and detect compile
    storms under concurrent traffic.
    """

    hits: int
    misses: int
    size: int
    keys: tuple[tuple[str, str, str | None, str, str | None], ...]


# Explicit dict cache (not lru_cache): the serving runtime calls
# get_accelerator from many replica/scheduler threads at once, and a bare
# lru_cache lets two concurrent misses BOTH construct (and later jit) an
# accelerator — a compile storm under traffic.  The lock serialises
# construction only; compiled infer/forward calls never take it.
_lock = threading.Lock()
_artifacts: dict[tuple, PC2IMAccelerator] = {}
_hits = 0
_misses = 0


def get_accelerator(
    config: PN.PointNet2Config, policy: ExecutionPolicy | None = None
) -> PC2IMAccelerator:
    """Accelerator cache: one compiled pipeline per (config, policy) pair.

    The policy is resolved against the config BEFORE keying the cache, so
    `get_accelerator(cfg)`, `get_accelerator(cfg, policy_for(cfg))` and a
    backend=None policy that resolves to the same concrete backend all share
    one artifact.  Thread-safe: concurrent callers with the same key always
    receive the same instance.
    """
    global _hits, _misses
    key = (config, resolve_policy(config, policy))
    with _lock:
        accel = _artifacts.get(key)
        if accel is None:
            _misses += 1
            accel = _artifacts[key] = PC2IMAccelerator(*key)
        else:
            _hits += 1
        return accel


def cache_stats() -> CacheStats:
    """Introspect the accelerator cache (hit/miss counters + live keys)."""
    with _lock:
        keys = tuple(
            (cfg.name, pol.quant, pol.backend, pol.pipeline, pol.sharding)
            for cfg, pol in _artifacts
        )
        return CacheStats(hits=_hits, misses=_misses, size=len(_artifacts), keys=keys)


def clear_cache() -> None:
    """Drop every cached accelerator and reset the hit/miss counters.

    Compiled engines keep their own cache (core.engine.get_engine); only the
    accelerator artifacts and counters are cleared here.
    """
    global _hits, _misses
    with _lock:
        _artifacts.clear()
        _hits = 0
        _misses = 0
