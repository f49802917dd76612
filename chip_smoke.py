"""Smoke run of the served PointNet2 path on a TPU.

    python chip_smoke.py               # one chip: cls served + seg batch
    python chip_smoke.py --chips 4     # mesh replicas and 4 one-chip replicas

The default run serves the full-width `pointnet2-cls` config through the
normal entry points (ServingRuntime -> ReplicaPool -> PC2IMAccelerator) with
the Pallas kernels compiled by Mosaic, replays every served micro-batch
through the plain-jnp XLA path on the same chip, and runs one full-width
`pointnet2-seg` batch the same way, at the S3DIS sizes of
`pointnet2_sem_seg.py` (four SA and four FP levels).  Checks:

  * the backend is a TPU and "auto" resolves to compiled Pallas kernels;
  * every served artifact's lowered text holds `tpu_custom_call`, and the
    seg artifact calls the fused 3-NN kernel `pc2im_knn3`;
  * every future resolves, with no retry, eviction, shed or rejection;
  * preprocess indices equal the XLA reference bit for bit;
  * each FP stage's 3-NN indices and distances equal the XLA path's bits;
  * logits agree with it to 1e-4 x max|logit|.

`--chips 4` runs only the multi-chip path (mesh replicas under "batch" and
"tensor" sharding, fp32 and SC W16A16, and four one-chip replicas) and
compares each against single-device `accel.infer` on device 0.

Timings printed here are smoke timings, not benchmark numbers.  The last
line of stdout is one JSON object naming the device; it is printed only when
every check passed.  Everything runs in this one process: the chip belongs
to the process that first touches JAX.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import pointnet2_cls, pointnet2_seg  # noqa: E402
from repro.core.accelerator import get_accelerator  # noqa: E402
from repro.core.policy import ExecutionPolicy  # noqa: E402
from repro.kernels import registry  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import pointnet2 as PN  # noqa: E402
from repro.serve.runtime import RuntimeConfig, ServingRuntime  # noqa: E402

REL_TOL = 1e-4  # logits: |served - reference| <= REL_TOL * max|reference|
SC = "sc_w16a16"
CLOUD_SIZES = (700, 1024, 1500)  # pad, exact, subsample at the 1024 bucket
#: pointnet2_sem_seg.py on 4096-point S3DIS blocks: four SA and four FP levels
SEG_S3DIS = dataclasses.replace(
    pointnet2_seg.CONFIG,
    n_classes=13,
    sa=(
        PN.SAConfig(1024, 0.1, 32, (32, 32, 64)),
        PN.SAConfig(256, 0.2, 32, (64, 64, 128)),
        PN.SAConfig(64, 0.4, 32, (128, 128, 256)),
        PN.SAConfig(16, 0.8, 32, (256, 256, 512)),
    ),
    fp_mlp=(256, 256, 256, 128),
)


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    """Raise SmokeFailure naming `what` unless `ok`."""
    if not ok:
        raise SmokeFailure(what)


class BatchLog:
    """Pool hook (the `ReplicaPool.chaos` seam) that records every real batch.

    Observation only: it never raises, so it injects no fault.
    """

    def __init__(self):
        self.batches = []

    def on_batch(self, pool, rep, mb) -> None:
        """Record one micro-batch about to execute on replica `rep`."""
        self.batches.append((rep.id, mb))


def make_clouds(n: int, seed: int) -> list[np.ndarray]:
    """`n` ragged unit-cube clouds cycling through CLOUD_SIZES."""
    rng = np.random.default_rng(seed)
    return [
        rng.uniform(-1.0, 1.0, (CLOUD_SIZES[i % len(CLOUD_SIZES)], 3)).astype(np.float32)
        for i in range(n)
    ]


def max_rel_err(out, ref) -> float:
    """max|out - ref| / max|ref| (the bound the logits are held to)."""
    out, ref = np.asarray(out), np.asarray(ref)
    return float(np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-30))


def compare_preprocess(pre, ref_pre, what: str) -> None:
    """Centroid and neighbour indices (and masks) must be bitwise-equal."""
    for stage, (a, b) in enumerate(zip(pre, ref_pre)):
        for name, x, y in (
            ("centroid_idx", a.centroid_idx, b.centroid_idx),
            ("neighbor idx", a.neighbors.idx, b.neighbors.idx),
            ("neighbor mask", a.neighbors.mask, b.neighbors.mask),
        ):
            check(
                np.array_equal(np.asarray(x), np.asarray(y)),
                f"{what}: SA stage {stage} {name} differs from the XLA reference",
            )


def xla_twin(policy: ExecutionPolicy | None) -> ExecutionPolicy:
    """The plain-jnp XLA reference of a served policy (same numeric mode)."""
    return ExecutionPolicy(quant=policy.quant if policy else "none", backend="xla")


def assert_kernels_compiled(accel, params, batch_shape, kernels=()) -> None:
    """The artifact's lowered text must call Mosaic kernels, not an XLA path.

    Each name in `kernels` must be among the Mosaic kernels it calls.
    """
    spec = jax.ShapeDtypeStruct(batch_shape, jnp.float32)
    text = jax.jit(accel.infer).lower(params, spec).as_text()
    check("tpu_custom_call" in text, f"{accel!r}: no tpu_custom_call in artifact")
    for name in kernels:
        check(f'kernel_name = "{name}"' in text, f"{accel!r}: no {name} kernel in artifact")


def serve_phase(cfg, params, policies, clouds, config: RuntimeConfig) -> dict:
    """Serve `clouds` through a ServingRuntime, alternating `policies`.

    Returns {"runtime", "log", "warmup_s", "serve_s"}; raises SmokeFailure
    on any failed future or any retry, eviction, shed, rejection or expiry.
    """
    rt = ServingRuntime(cfg, params, config)
    log = BatchLog()
    rt.pool.chaos = log
    t0 = time.perf_counter()
    rt.warmup(policies=tuple(policies))
    warmup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    with rt:
        futures = [
            rt.submit(c, policy=policies[i % len(policies)]) for i, c in enumerate(clouds)
        ]
        for f in futures:
            check(f.exception(timeout=600) is None, f"served request failed: {f.exception()}")
    serve_s = time.perf_counter() - t0
    snap = rt.metrics.snapshot()
    for field in ("failed", "retries", "evictions", "shed", "rejected", "expired"):
        check(getattr(snap, field) == 0, f"serving recorded {field}={getattr(snap, field)}")
    check(snap.completed == len(clouds), f"completed {snap.completed} of {len(clouds)}")
    return {"runtime": rt, "log": log, "warmup_s": warmup_s, "serve_s": serve_s}


def replay_phase(cfg, params, log: BatchLog, reference) -> dict[str, tuple[float, bool]]:
    """Re-run every served micro-batch through `reference(policy)`.

    Each request's served logits must lie within REL_TOL of the reference
    rows.  Returns {policy key: (max relative error, bitwise-equal?)}.
    """
    out: dict[str, tuple[float, bool]] = {}
    for _, mb in log.batches:
        ref = np.asarray(reference(mb.policy).infer(params, jnp.asarray(mb.batch)))
        served = np.stack([req.future.result() for req in mb.requests])
        key = "/".join(x for x in (mb.policy.sharding, mb.policy.quant) if x)
        err = max_rel_err(served, ref[: mb.n_real])
        check(err <= REL_TOL, f"{cfg.name} {key}: logits off by {err:.3g}")
        prev_err, prev_same = out.get(key, (0.0, True))
        out[key] = (max(prev_err, err), prev_same and np.array_equal(served, ref[: mb.n_real]))
    return out


def preprocess_phase(cfg, log: BatchLog) -> None:
    """Every served batch's preprocess indices equal the XLA path's bits."""
    for _, mb in log.batches:
        batch = jnp.asarray(mb.batch)
        compare_preprocess(
            get_accelerator(cfg, mb.policy).preprocess_stage(batch),
            get_accelerator(cfg, xla_twin(mb.policy)).preprocess_stage(batch),
            f"{cfg.name} {mb.policy.quant} batch",
        )


def direct_phase(cfg, params, policy, batch) -> float:
    """One batch through `accel.infer` vs the XLA reference; returns the error."""
    accel = get_accelerator(cfg, policy)
    ref = get_accelerator(cfg, xla_twin(accel.policy))
    compare_preprocess(accel.preprocess_stage(batch), ref.preprocess_stage(batch), cfg.name)
    err = max_rel_err(accel.infer(params, batch), ref.infer(params, batch))
    check(err <= REL_TOL, f"{cfg.name}: logits off by {err:.3g}")
    return err


def fp_knn_phase(cfg, policy, batch) -> int:
    """Each FP stage's 3-NN under `policy` equals the XLA path's, bit for bit.

    The stages search the SA pyramid of `batch` (the XLA path's centroids),
    finest last, as `feature_stage` does.  Returns the number of stages.
    """
    policy = get_accelerator(cfg, policy).policy
    pre = get_accelerator(cfg, xla_twin(policy)).preprocess_stage(batch)
    levels = [batch[..., :3]] + [r.centroid_xyz for r in pre]
    fp_knn = jax.jit(PN.fp_knn, static_argnums=2)
    for stage, i in enumerate(range(len(levels) - 1, 0, -1), 1):
        got = fp_knn(levels[i - 1], levels[i], policy)
        want = fp_knn(levels[i - 1], levels[i], xla_twin(policy))
        for name, a, b in zip(("idx", "dist"), got, want):
            check(
                np.array_equal(np.asarray(a), np.asarray(b)),
                f"{cfg.name} fp{stage}: 3-NN {name} differs from the XLA path",
            )
    return len(levels) - 1


def report(label: str, results: dict[str, tuple[float, bool]]) -> None:
    """One line per policy key: the error bound and whether it is bitwise."""
    for key, (err, same) in sorted(results.items()):
        print(
            f"{label} {key}: max|logit diff| / max|logit| = {err:.3e}, "
            f"{'bitwise-equal' if same else 'not bitwise'}"
        )


def run_one_chip(seed: int) -> None:
    """Default run: full-width cls served, then one full-width seg batch."""
    cfg = pointnet2_cls.CONFIG
    params = jax.jit(get_accelerator(cfg).init)(jax.random.PRNGKey(seed))
    policies = (None, ExecutionPolicy(quant=SC))
    for pol in policies:
        assert_kernels_compiled(get_accelerator(cfg, pol), params, (8, cfg.n_points, 3))
    res = serve_phase(
        cfg, params, policies, make_clouds(32, seed),
        RuntimeConfig(max_batch=8, n_replicas=1),
    )
    print(f"cls compile set-up (warmup, 2 policies): {res['warmup_s']:.1f} s")
    print(
        f"cls served 32 clouds in {len(res['log'].batches)} micro-batches, "
        f"{res['serve_s']:.2f} s (smoke timing, not a benchmark)"
    )
    preprocess_phase(cfg, res["log"])
    print("cls preprocess indices: bitwise-equal to the XLA reference")
    report("cls vs XLA reference", replay_phase(
        cfg, params, res["log"], lambda pol: get_accelerator(cfg, xla_twin(pol))
    ))

    seg = SEG_S3DIS
    sparams = jax.jit(get_accelerator(seg).init)(jax.random.PRNGKey(seed + 1))
    assert_kernels_compiled(
        get_accelerator(seg), sparams, (8, seg.n_points, 3), kernels=("pc2im_knn3",)
    )
    sbatch = jax.random.uniform(
        jax.random.PRNGKey(seed + 2), (8, seg.n_points, 3), minval=-1.0, maxval=1.0
    )
    t0 = time.perf_counter()
    err = direct_phase(seg, sparams, None, sbatch)
    print(
        f"seg fp32 B=8 x {seg.n_points} pts: preprocess indices bitwise-equal, "
        f"max|logit diff| / max|logit| = {err:.3e} vs XLA reference "
        f"({time.perf_counter() - t0:.1f} s incl. compile, smoke timing)"
    )
    n_fp = fp_knn_phase(seg, None, sbatch)
    print(f"seg FP 3-NN, {n_fp} stages: idx and dist bitwise-equal to the XLA path")


def run_four_chips(seed: int) -> None:
    """Mesh replicas (batch, tensor x fp32, SC) and four one-chip replicas.

    Each is compared with single-device `accel.infer` on device 0.
    """
    devs = jax.devices()
    check(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    cfg = pointnet2_cls.CONFIG
    params = jax.jit(get_accelerator(cfg).init)(jax.random.PRNGKey(seed))
    clouds = make_clouds(32, seed)

    def on_device0(pol):
        return get_accelerator(cfg, ExecutionPolicy(quant=pol.quant))

    sharded = [
        ExecutionPolicy(quant=q, sharding=m) for m in ("batch", "tensor") for q in ("none", SC)
    ]
    res = serve_phase(
        cfg, params, sharded, clouds, RuntimeConfig(max_batch=8, devices_per_replica=4)
    )
    rep = res["runtime"].pool.replicas[0]
    check(len(rep.devices) == 4, f"mesh replica spans {len(rep.devices)} devices")
    for pol in sharded:
        out = get_accelerator(cfg, pol).mesh_artifacts(rep.devices).infer(
            rep.mesh_params, jnp.zeros((8, cfg.n_points, 3), jnp.float32)
        )
        check(len(out.sharding.device_set) == 4, f"{pol}: output not on 4 devices")
    report("mesh replica vs device 0", replay_phase(cfg, params, res["log"], on_device0))

    res = serve_phase(
        cfg, params, (None, ExecutionPolicy(quant=SC)), clouds, RuntimeConfig(max_batch=8)
    )
    replicas = res["runtime"].pool.replicas
    check(len(replicas) == 4, f"expected 4 one-chip replicas, got {len(replicas)}")
    for r in replicas:
        placed = {d for leaf in jax.tree.leaves(r.params) for d in leaf.devices()}
        check(placed == {r.device}, f"replica {r.id} params on {placed}, not {r.device}")
    used = sorted({rid for rid, _ in res["log"].batches})
    print(f"4 one-chip replicas: batches ran on replicas {used}")
    report("one-chip replica vs device 0", replay_phase(cfg, params, res["log"], on_device0))
    for d in devs:
        check((d.memory_stats() or {}).get("bytes_in_use", 0) > 0, f"{d}: no bytes in use")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {backend!r}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    try:
        check(
            registry.resolve_backend("auto") == ("pallas", False),
            f"'auto' resolves to {registry.resolve_backend('auto')}, not compiled Pallas",
        )
        dev = jax.devices()[0]
        print(f"device_kind={dev.device_kind} devices={len(jax.devices())} cache={cache_dir}")
        t0 = time.perf_counter()
        if args.chips == 4:
            run_four_chips(args.seed)
        else:
            run_one_chip(args.seed)
        print(f"total smoke wall time {time.perf_counter() - t0:.1f} s (smoke timing)")
        for d in jax.devices()[: args.chips]:
            stats = d.memory_stats() or {}
            print(f"{d}: peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": args.chips},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
