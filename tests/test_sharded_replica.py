"""Mesh-sharded replicas: parity, carving, cache keys, chaos on a group.

The tentpole claim is bitwise: a replica that owns a device GROUP and runs
the sharded artifact (batch-sharded preprocess, or tensor-sharded feature
MLPs with concatenated partials — the paper's split-concatenate dataflow)
returns exactly the bits the single-device artifact returns, for fp32 AND
SC-quantized policies.  On the CPU backend an fp32 dot's bits depend on its
width, so fp32 tensor mode is compared with a single-device forward whose
linears multiply in the same column blocks.  Host-side tests cover the pure math (group carving,
policy validation, cache-key isolation, assemble/scatter shard locality);
the multi-device proofs run in forced-host-device subprocesses via
tests/_multidev.py, with the parity asserts living HERE in the parent.
"""

import numpy as np
import pytest

from _hypothesis import given, settings, st
from _multidev import assert_bitwise, run_in_child

from repro.configs.base import get_config
from repro.core.accelerator import cache_stats, clear_cache, get_accelerator
from repro.core.policy import ExecutionPolicy
from repro.launch.mesh import carve_device_groups
from repro.serve.queue import Request
from repro.serve.scheduler import MicroBatch, assemble_batch, scatter_results


# -- device-group carving (pure math: works on plain ints) --------------------


class TestCarving:
    def test_exact_division(self):
        assert carve_device_groups([0, 1, 2, 3], 2) == [(0, 1), (2, 3)]

    def test_per_one_is_classic_replicas(self):
        assert carve_device_groups([0, 1, 2], 1) == [(0,), (1,), (2,)]

    def test_whole_fleet_is_one_group(self):
        assert carve_device_groups([0, 1, 2, 3], 4) == [(0, 1, 2, 3)]

    def test_leftover_devices_unused(self):
        # 4 devices / groups of 3: one group, the tail is left idle rather
        # than forming a ragged (differently-shaped, differently-traced) mesh
        assert carve_device_groups([0, 1, 2, 3], 3) == [(0, 1, 2)]

    def test_group_larger_than_fleet_raises(self):
        with pytest.raises(ValueError):
            carve_device_groups([0, 1], 3)

    def test_nonpositive_group_raises(self):
        with pytest.raises(ValueError):
            carve_device_groups([0, 1], 0)


# -- the ExecutionPolicy.sharding knob ----------------------------------------


class TestShardingKnob:
    def test_invalid_mode_rejected(self):
        with pytest.raises(ValueError, match="sharding"):
            ExecutionPolicy(sharding="bogus")

    def test_sharding_excludes_pipelined_schedule(self):
        # both knobs re-partition the same computation; composing them is
        # undefined and refused at construction, not at trace time
        with pytest.raises(ValueError, match="pipeline"):
            ExecutionPolicy(sharding="batch", pipeline="pipelined")

    def test_replica_specs_modes(self):
        from jax.sharding import PartitionSpec as P

        from repro.sharding.hints import REPLICA_AXIS
        from repro.sharding.policy import replica_specs

        for mode in ("batch", "tensor"):
            p_params, p_points, p_logits = replica_specs(mode)
            assert p_params == P()  # params replicated over the group
            assert p_points == P(REPLICA_AXIS)
            assert p_logits == P(REPLICA_AXIS)
        with pytest.raises(ValueError):
            replica_specs("bogus")

    def test_replica_axis_active_only_inside_replica_mesh(self):
        """The knob's inertness rests on this probe: the axis is unbound
        under plain jit and bound inside shard_map over REPLICA_AXIS."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import make_replica_mesh
        from repro.sharding.hints import replica_axis_active

        seen = []

        def probe(x):
            seen.append(replica_axis_active())
            return x

        jax.jit(probe)(jnp.zeros(2))
        mesh = make_replica_mesh(jax.devices()[:1])
        jax.jit(
            jax.shard_map(probe, mesh=mesh, in_specs=P(), out_specs=P())
        )(jnp.zeros(2))
        assert seen == [False, True]

    def test_cache_key_isolation(self):
        """sharding hashes into the artifact cache exactly like pipeline
        does: unsharded / batch / tensor traffic get three artifacts."""
        clear_cache()
        cfg = get_config("pointnet2-cls", smoke=True)
        get_accelerator(cfg)
        get_accelerator(cfg, ExecutionPolicy(sharding="batch"))
        get_accelerator(cfg, ExecutionPolicy(sharding="tensor"))
        stats = cache_stats()
        assert stats.size == 3
        assert {k[4] for k in stats.keys} == {None, "batch", "tensor"}
        # repeat lookups hit, never re-trace
        get_accelerator(cfg, ExecutionPolicy(sharding="batch"))
        assert cache_stats().size == 3

    def test_mesh_artifacts_requires_sharded_policy(self):
        import jax

        clear_cache()
        cfg = get_config("pointnet2-cls", smoke=True)
        accel = get_accelerator(cfg)  # sharding=None
        with pytest.raises(ValueError, match="sharding"):
            accel.mesh_artifacts(jax.devices()[:1])


# -- assemble/scatter shard locality (hypothesis property) --------------------


WIDTH = 6  # 3 coords + 3 features; any fixed width works
N_CLASSES = 5


@given(st.data())
@settings(max_examples=30, deadline=None)
def test_assemble_scatter_row_locality_under_any_split(data):
    """Batch-sharding correctness reduces to row locality: for ANY split of
    the static batch dim into contiguous chunks (ragged tails included),
    assembling each chunk's requests alone reproduces that chunk of the full
    assembly bitwise, and scattering each chunk's logits alone reproduces
    the full scatter — so a mesh shard that sees only its row block computes
    exactly what the unsharded batch would have handed it."""
    bucket = data.draw(st.sampled_from([32, 64]))
    n_req = data.draw(st.integers(min_value=1, max_value=6))
    # cloud sizes straddle the bucket: padded, exact, and subsampled rows
    sizes = data.draw(
        st.lists(
            st.integers(min_value=1, max_value=2 * bucket),
            min_size=n_req,
            max_size=n_req,
        )
    )
    max_batch = n_req + data.draw(st.integers(min_value=0, max_value=3))
    cuts = (
        data.draw(
            st.lists(
                st.integers(min_value=1, max_value=max_batch - 1),
                unique=True,
                max_size=3,
            )
        )
        if max_batch > 1
        else []
    )
    bounds = [0] + sorted(cuts) + [max_batch]
    task = data.draw(st.sampled_from(["cls", "seg"]))
    seed = data.draw(st.integers(min_value=0, max_value=2**31 - 1))

    rng = np.random.default_rng(seed)
    reqs = [
        Request(
            id=i,
            cloud=rng.standard_normal((n, WIDTH)).astype(np.float32),
            n_orig=n,
            bucket=bucket,
            policy=None,
            deadline_t=None,
            submit_t=0.0,
            future=None,
        )
        for i, n in enumerate(sizes)
    ]

    full = assemble_batch(reqs, bucket, WIDTH, max_batch)
    for lo, hi in zip(bounds, bounds[1:]):
        chunk = assemble_batch(reqs[lo:hi], bucket, WIDTH, hi - lo)
        np.testing.assert_array_equal(chunk, full[lo:hi])

    shape = (max_batch, bucket, N_CLASSES) if task == "seg" else (max_batch, N_CLASSES)
    logits = rng.standard_normal(shape).astype(np.float32)
    whole = scatter_results(
        task, logits, MicroBatch(tuple(reqs), bucket, None, full)
    )
    pieces = []
    for lo, hi in zip(bounds, bounds[1:]):
        sub = MicroBatch(tuple(reqs[lo:hi]), bucket, None, full[lo:hi])
        pieces.extend(scatter_results(task, logits[lo:hi], sub))
    assert len(whole) == len(pieces) == len(reqs)
    for a, b in zip(whole, pieces):
        np.testing.assert_array_equal(a, b)


# -- sharded-vs-single-device bitwise parity (8 forced host devices) ----------


def test_sharded_parity_all_modes_subprocess():
    """Every (mode x quant x group-size) sharded artifact is bitwise-equal
    to the single-device artifact of the same quant policy, on the same
    batch.  batch mode needs the pmax-globalized activation amax; tensor
    mode needs the full-weight (global-scale) quantization before the
    integer column slice — this test pins both.

    fp32 tensor mode multiplies each device's column block as a dot of
    that block's width, and the CPU backend picks a dot's summation order
    by its shape: a narrow dot is not bitwise the same columns of a wide
    one (pinned below, with the row independence the B=1 references of
    the serving tests rely on).  Its reference is therefore the
    single-device forward with every fp32 linear multiplied in the same
    column blocks; everything else in it is the single-device program."""
    payload = run_in_child(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import get_config
        from repro.core.accelerator import PC2IMAccelerator, get_accelerator
        from repro.core.policy import ExecutionPolicy
        from repro.models import nn

        def column_blocked_infer(cfg, params, pts, group):
            # single-device fp32 forward; each linear multiplies in the
            # column blocks a tensor-sharded group of `group` devices uses
            def blocked(p, x, policy=None):
                w = p["w"]
                n = w.shape[1]
                cols = -(-n // group)
                wp = jnp.pad(w, ((0, 0), (0, cols * group - n)))
                y = jnp.concatenate(
                    [x @ wp[:, i * cols:(i + 1) * cols] for i in range(group)],
                    axis=-1,
                )[..., :n]
                return y + p["b"] if "b" in p else y

            plain, nn.linear = nn.linear, blocked
            try:
                return PC2IMAccelerator(cfg).infer(params, pts)
            finally:
                nn.linear = plain

        # the CPU dot's shape dependence, in isolation
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((512, 64)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
        matmul = jax.jit(lambda a, b: a @ b)
        emit("dot_wide_cols", matmul(x, w)[:, :16])
        emit("dot_narrow", matmul(x, w[:, :16]))
        emit("dot_rows", matmul(x, w)[:1])
        emit("dot_one_row", matmul(x[:1], w))

        cfg = get_config("pointnet2-cls", smoke=True)
        base = get_accelerator(cfg)
        params = base.init(jax.random.PRNGKey(0))
        pts = np.asarray(
            jax.random.normal(
                jax.random.PRNGKey(1), (8, cfg.n_points, 3 + cfg.in_features)
            ),
            np.float32,
        )
        for quant in ("none", "sc_w16a16"):
            ref = get_accelerator(cfg, ExecutionPolicy(quant=quant)).infer(
                params, pts
            )
            emit(f"ref_{quant}", ref)
            for mode in ("batch", "tensor"):
                accel = get_accelerator(
                    cfg, ExecutionPolicy(quant=quant, sharding=mode)
                )
                for g in (2, 8):
                    arts = accel.mesh_artifacts(jax.devices()[:g])
                    emit(f"out_{quant}_{mode}_{g}", arts.infer(params, pts))
        for g in (2, 8):
            emit(f"ref_none_tensor_{g}", column_blocked_infer(cfg, params, pts, g))

        # seg head through tensor sharding: per-point logits concatenate the
        # same way, and the out-spec row slice round-trips (4 rows / 4 shards)
        seg = get_config("pointnet2-seg", smoke=True)
        sbase = get_accelerator(seg)
        sparams = sbase.init(jax.random.PRNGKey(2))
        spts = np.asarray(
            jax.random.normal(
                jax.random.PRNGKey(3), (4, seg.n_points, 3 + seg.in_features)
            ),
            np.float32,
        )
        emit("seg_ref", column_blocked_infer(seg, sparams, spts, 4))
        sarts = get_accelerator(
            seg, ExecutionPolicy(sharding="tensor")
        ).mesh_artifacts(jax.devices()[:4])
        emit("seg_out", sarts.infer(sparams, spts))
        """,
        n_devices=8,
    )
    assert not np.array_equal(payload["dot_narrow"], payload["dot_wide_cols"])
    assert_bitwise(payload, "dot_one_row", "dot_rows")
    for g in (2, 8):
        for quant in ("none", "sc_w16a16"):
            assert_bitwise(payload, f"out_{quant}_batch_{g}", f"ref_{quant}")
        assert_bitwise(payload, f"out_sc_w16a16_tensor_{g}", "ref_sc_w16a16")
        assert_bitwise(payload, f"out_none_tensor_{g}", f"ref_none_tensor_{g}")
    assert_bitwise(payload, "seg_out", "seg_ref")


# -- ReplicaPool over device groups: carving, warmup, chaos, warm rejoin ------


def test_mesh_replica_pool_chaos_subprocess():
    """ReplicaPool carves 4 devices into two 2-device mesh replicas; every
    (bucket x policy) warmup artifact is bitwise-correct on BOTH groups;
    chaos kill and heartbeat-detected wedge each evict a mesh replica, and
    rejoin reuses the cached per-group artifacts (warm: no re-trace)."""
    payload = run_in_child(
        """
        import time

        import jax, numpy as np
        from repro.configs.base import get_config
        from repro.core.accelerator import get_accelerator
        from repro.core.policy import ExecutionPolicy
        from repro.serve.chaos import ChaosInjector, Fault
        from repro.serve.runtime import RuntimeConfig, ServingRuntime

        cfg = get_config("pointnet2-cls", smoke=True)
        base = get_accelerator(cfg)
        params = base.init(jax.random.PRNGKey(0))
        width = 3 + cfg.in_features
        pol_b = ExecutionPolicy(sharding="batch")  # fp32, batch-sharded
        pol_t = ExecutionPolicy(quant="sc_w16a16", sharding="tensor")
        buckets = (192, cfg.n_points)

        rt = ServingRuntime(
            cfg,
            params,
            RuntimeConfig(max_batch=4, devices_per_replica=2, buckets=buckets),
            policy=pol_b,
        )
        devs = jax.devices()
        assert [r.devices for r in rt.pool.replicas] == [
            tuple(devs[:2]),
            tuple(devs[2:4]),
        ], rt.pool.replicas
        rt.warmup((pol_b, pol_t))

        # every (bucket x policy) warmup artifact, on every group, is
        # bitwise-equal to the single-device artifact of the same quant
        rng = np.random.default_rng(0)
        for pi, pol in enumerate((pol_b, pol_t)):
            accel = get_accelerator(cfg, pol)
            ref_accel = get_accelerator(cfg, ExecutionPolicy(quant=pol.quant))
            for bucket in buckets:
                batch = rng.standard_normal((4, bucket, width)).astype(np.float32)
                emit(f"warm_ref_{pi}_{bucket}", ref_accel.infer(params, batch))
                for rep in rt.pool.replicas:
                    arts = accel.mesh_artifacts(rep.devices)
                    emit(
                        f"warm_{pi}_{bucket}_{rep.id}",
                        arts.infer(rep.mesh_params, batch),
                    )

        # end-to-end submits through the sharded dispatch path (fp32 forward
        # is batch-size independent bitwise, so B=1 unsharded refs are exact)
        clouds = [
            rng.standard_normal((cfg.n_points, width)).astype(np.float32)
            for _ in range(12)
        ]
        with rt:
            outs = [
                f.result(timeout=120) for f in [rt.submit(c) for c in clouds]
            ]
        emit("live_out", np.stack(outs))
        emit("live_ref", np.stack(
            [np.asarray(base.infer(params, c[None]))[0] for c in clouds]
        ))

        # chaos kill on a mesh replica -> evict -> warm rejoin on same group
        accel_b = get_accelerator(cfg, pol_b)
        rt2 = ServingRuntime(
            cfg,
            params,
            RuntimeConfig(max_batch=4, devices_per_replica=2),
            policy=pol_b,
        )
        rt2.warmup((pol_b,))
        group1 = rt2.pool.replicas[1].devices
        arts_before = accel_b.mesh_artifacts(group1)
        ChaosInjector([Fault(replica_id=1, at_batch=0, kind="kill")]).attach(
            rt2.pool
        )
        with rt2:
            outs = [
                f.result(timeout=120) for f in [rt2.submit(c) for c in clouds[:8]]
            ]
            assert sum(1 for r in rt2.pool.replicas if r.alive) == 1
            assert rt2.pool.rejoin(1)
            rep1 = rt2.pool.replicas[1]
            assert rep1.alive and rep1.devices == group1
            # warm: the rejoined replica resolves the SAME cached per-group
            # artifacts object -> zero re-tracing on rejoin
            assert accel_b.mesh_artifacts(rep1.devices) is arts_before
            outs += [
                f.result(timeout=120) for f in [rt2.submit(c) for c in clouds[8:]]
            ]
        emit("kill_out", np.stack(outs))

        # wedge: the injector hangs a mesh replica's worker thread; the
        # heartbeat monitor (not the injector) detects it and evicts
        rt3 = ServingRuntime(
            cfg,
            params,
            RuntimeConfig(
                max_batch=4, devices_per_replica=2, heartbeat_timeout_s=0.25
            ),
            policy=pol_b,
        )
        rt3.warmup((pol_b,))
        ChaosInjector(
            [Fault(replica_id=0, at_batch=0, kind="wedge", duration_s=1.5)]
        ).attach(rt3.pool)
        with rt3:
            outs = [
                f.result(timeout=120) for f in [rt3.submit(c) for c in clouds[:8]]
            ]
            deadline = time.monotonic() + 60
            while (
                sum(1 for r in rt3.pool.replicas if r.alive) == 2
                and time.monotonic() < deadline
            ):
                time.sleep(0.02)
            assert sum(1 for r in rt3.pool.replicas if r.alive) == 1
            assert rt3.metrics.evictions >= 1
            assert rt3.pool.rejoin(0)
            outs += [
                f.result(timeout=120) for f in [rt3.submit(c) for c in clouds[8:]]
            ]
        emit("wedge_out", np.stack(outs))
        """,
        n_devices=4,
    )
    assert_bitwise(payload, "live_out", "live_ref")
    assert_bitwise(payload, "kill_out", "live_ref")
    assert_bitwise(payload, "wedge_out", "live_ref")
