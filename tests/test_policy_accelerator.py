"""ExecutionPolicy + PC2IMAccelerator: the explicit config->artifact API.

Covers the redesign's contract:
  * policies are hashable, validated, and passed functionally — NO
    thread-local/module-global quant state anywhere in src/ (grep-enforced);
  * the policy-quantized `nn.linear` is bitwise-identical to the former
    `quant_mode` path (core.quant.quantized_linear);
  * PC2IMAccelerator compiles one artifact per (config, policy), its infer
    matches a hand-jitted policy forward bitwise, and serve_batch runs
    through the accelerator artifact;
  * two threads under DIFFERENT policies produce independent, correct
    results — the exact failure mode the thread-local API allowed.
"""

import concurrent.futures
import dataclasses
import pathlib
import re

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.accelerator import get_accelerator
from repro.core.policy import ExecutionPolicy, policy_for
from repro.core.quant import quantized_linear
from repro.data.pointclouds import sample_batch
from repro.models import nn
from repro.models import pointnet2 as PN

jax.config.update("jax_platform_name", "cpu")

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


class TestExecutionPolicy:
    def test_hashable_and_cache_key(self):
        a = ExecutionPolicy(quant="sc_w16a16", backend="xla")
        b = ExecutionPolicy(quant="sc_w16a16", backend="xla")
        assert a == b and hash(a) == hash(b)
        assert len({a, b, ExecutionPolicy()}) == 2

    def test_validation(self):
        with pytest.raises(ValueError, match="quant"):
            ExecutionPolicy(quant="w4a4")
        with pytest.raises(ValueError, match="backend"):
            ExecutionPolicy(backend="cuda")

    def test_quant_bits(self):
        assert ExecutionPolicy().quant_bits is None
        assert ExecutionPolicy(quant="sc_w16a16").quant_bits == 16
        assert ExecutionPolicy(quant="sc_w8a8").quant_bits == 8

    def test_policy_for_reads_config(self):
        cfg = get_config("pointnet2-cls", smoke=True)
        cfg = dataclasses.replace(cfg, quant="sc_w16a16", preproc_backend="xla")
        pol = policy_for(cfg)
        assert pol.quant == "sc_w16a16" and pol.backend == "xla"

    def test_quant_mode_shim_deprecated(self):
        """The one-release compatibility shim yields the equivalent policy,
        warning loudly (FutureWarning shows by default) that quantization is
        no longer applied implicitly."""
        with pytest.warns(FutureWarning, match="no longer applies"):
            with nn.quant_mode("sc_w16a16") as pol:
                assert pol == ExecutionPolicy(quant="sc_w16a16")

    def test_backend_none_defers_to_config(self):
        """A policy that only sets quant must NOT discard the config's pinned
        preproc_backend: backend=None resolves against the config ONCE, so
        BOTH halves (engines and SC feature path) get the same backend."""
        from repro.core.policy import resolve_policy
        from repro.models.pointnet2 import stage_engine

        cfg = get_config("pointnet2-cls", smoke=True)
        cfg = dataclasses.replace(cfg, preproc_backend="xla")
        pol = ExecutionPolicy(quant="sc_w16a16")  # backend unspecified
        assert pol.backend is None
        assert resolve_policy(cfg, pol).backend == "xla"
        eng = stage_engine(cfg, cfg.sa[0], cfg.n_points, pol)
        assert eng.config.backend == "xla"
        # the accelerator resolves at construction (feature path included)
        # and the cache treats the unresolved and resolved forms as one
        accel = get_accelerator(cfg, pol)
        assert accel.policy.backend == "xla"
        assert accel is get_accelerator(cfg, dataclasses.replace(pol, backend="xla"))


class TestNoHiddenState:
    # The only threading.locals allowed in src/: the kernel registry's
    # documented trace-time backend override (tests-only escape hatch) and
    # the launcher's activation-sharding hint context.  Neither carries
    # quant state; the quant decision travels ONLY inside ExecutionPolicy.
    ALLOWED_THREAD_LOCALS = {
        "repro/kernels/registry.py",
        "repro/sharding/hints.py",
    }

    def test_no_thread_local_quant_state_in_src(self):
        """Grep-enforced: no thread-local/module-global quant state in src/;
        models/ and the quant path hold no mutable execution state."""
        offenders = []
        for path in sorted(SRC.rglob("*.py")):
            text = path.read_text()
            rel = str(path.relative_to(SRC))
            if re.search(r"threading\.local\(\)", text) and rel not in self.ALLOWED_THREAD_LOCALS:
                offenders.append(rel)
            if "models/" in rel and re.search(r"\bthreading\b", text):
                offenders.append(rel + " (threading in models/)")
        assert offenders == [], offenders

    def test_nn_has_no_module_state(self):
        assert not hasattr(nn, "_STATE")
        assert not hasattr(nn, "current_quant_mode")


class TestQuantizedLinearParity:
    def test_bitwise_vs_former_quant_mode_path(self):
        """nn.linear under an SC policy == the old thread-local path's math
        (core.quant.quantized_linear, f32 combine) bit for bit."""
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 4, 32))
        p = nn.linear_init(jax.random.PRNGKey(1), 32, 16)
        for bits, mode in ((16, "sc_w16a16"), (8, "sc_w8a8")):
            new = nn.linear(p, x, policy=ExecutionPolicy(quant=mode, backend="xla"))
            old = quantized_linear(x, p["w"], bits=bits).astype(x.dtype) + p["b"]
            np.testing.assert_array_equal(np.asarray(new), np.asarray(old), err_msg=mode)

    def test_none_policy_is_float_path(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 8))
        p = nn.linear_init(jax.random.PRNGKey(1), 8, 8)
        np.testing.assert_array_equal(
            np.asarray(nn.linear(p, x)),
            np.asarray(nn.linear(p, x, policy=ExecutionPolicy())),
        )


class TestLayerNormOrder:
    @pytest.mark.parametrize("width", [1, 3, 64, 67, 768])
    def test_tree_sum_is_exact_sum(self, width):
        # small integers: every association order gives the exact sum
        x = jax.random.randint(jax.random.PRNGKey(0), (5, width), -100, 100)
        x = x.astype(np.float32)
        np.testing.assert_array_equal(
            np.asarray(nn.tree_sum(x)), np.asarray(x).sum(-1, keepdims=True)
        )

    def test_fixed_order_only_under_a_quantizing_policy(self):
        """An SC policy's LayerNorms sum with tree_sum (no reduce the
        compiler may reassociate ahead of a quantization); the float path
        keeps the plain reduce."""
        p = nn.mlp_init(jax.random.PRNGKey(0), [3, 64, 67])
        x = jax.numpy.ones((2, 16, 3))
        for policy, reduces in (
            (None, True),
            (ExecutionPolicy(quant="sc_w16a16", backend="xla"), False),
            (ExecutionPolicy(quant="sc_w8a8", backend="xla"), False),
        ):
            jaxpr = jax.make_jaxpr(lambda p, x: nn.mlp_apply(p, x, policy=policy))(p, x)
            assert ("reduce_sum" in str(jaxpr)) is reduces, policy


def _smoke_setup(quant="none", batch=2):
    cfg = get_config("pointnet2-cls", smoke=True)
    policy = ExecutionPolicy(quant=quant, backend="xla")
    accel = get_accelerator(cfg, policy)
    params = accel.init(jax.random.PRNGKey(0))
    pts, cls, _ = sample_batch(jax.random.PRNGKey(1), batch, cfg.n_points)
    return cfg, policy, accel, params, pts, cls


class TestAccelerator:
    def test_cache_one_artifact_per_config_policy(self):
        cfg = get_config("pointnet2-cls", smoke=True)
        assert get_accelerator(cfg) is get_accelerator(cfg)
        # default policy resolves before keying: explicit default == implicit
        assert get_accelerator(cfg) is get_accelerator(cfg, policy_for(cfg))
        other = get_accelerator(cfg, ExecutionPolicy(quant="sc_w16a16"))
        assert other is not get_accelerator(cfg)

    def test_engines_follow_sa_pyramid(self):
        cfg, _, accel, *_ = _smoke_setup()
        assert len(accel.engines) == len(cfg.sa)
        for eng, sa in zip(accel.engines, cfg.sa):
            assert eng.config.n_centroids == sa.n_centroids

    def test_infer_bitwise_matches_policy_forward(self):
        """Acceptance: the accelerator artifact == jitting the policy-threaded
        forward by hand (the rewired quant path changes no numerics)."""
        cfg, policy, accel, params, pts, _ = _smoke_setup(quant="sc_w16a16")
        got = accel.infer(params, pts)
        ref = jax.jit(lambda p, x: PN.forward(p, cfg, x, policy=policy))(params, pts)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_quant_close_to_float(self):
        cfg, _, accel_q, params, pts, _ = _smoke_setup(quant="sc_w16a16")
        accel_f = get_accelerator(cfg, ExecutionPolicy(backend="xla"))
        lq = np.asarray(accel_q.infer(params, pts))
        lf = np.asarray(accel_f.infer(params, pts))
        assert not np.array_equal(lq, lf)  # quant actually engaged
        assert np.abs(lq - lf).max() / (np.abs(lf).max() + 1e-9) < 1e-2

    def test_loss_artifact_and_grads(self):
        _, _, accel, params, pts, cls = _smoke_setup(quant="sc_w16a16")
        loss, metrics = accel.loss(params, pts, cls)
        assert np.isfinite(float(loss)) and "accuracy" in metrics
        grads = jax.grad(lambda p: accel.loss_fn(p, pts, cls)[0])(params)
        assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree.leaves(grads))

    def test_serve_batch_runs_through_accelerator(self):
        """serve_batch consumes the accelerator artifact (not an ad-hoc jit)."""
        from repro.serve import make_pointcloud_serve_fns

        cfg, policy, accel, params, _, _ = _smoke_setup(quant="sc_w16a16")
        fns = make_pointcloud_serve_fns(cfg, policy=policy)
        assert fns["accelerator"] is accel
        assert fns["infer"] == accel.infer
        clouds = [
            np.asarray(sample_batch(jax.random.PRNGKey(7 + i), 1, 200)[0][0])
            for i in range(3)
        ]
        out = fns["serve_batch"](params, clouds)
        assert len(out) == 3 and all(o.shape == (cfg.n_classes,) for o in out)


class TestConcurrentPolicies:
    def test_two_threads_two_policies_independent(self):
        """Regression for the thread-local API's failure mode: concurrent
        callers under different quant policies must each get exactly the
        result their own policy produces."""
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
        p = nn.linear_init(jax.random.PRNGKey(1), 64, 32)
        policies = {
            "none": None,
            "sc_w16a16": ExecutionPolicy(quant="sc_w16a16", backend="xla"),
            "sc_w8a8": ExecutionPolicy(quant="sc_w8a8", backend="xla"),
        }
        expected = {
            name: np.asarray(nn.linear(p, x, policy=pol))
            for name, pol in policies.items()
        }

        def worker(name):
            outs = []
            for _ in range(20):
                outs.append(np.asarray(nn.linear(p, x, policy=policies[name])))
            return name, outs

        with concurrent.futures.ThreadPoolExecutor(max_workers=3) as ex:
            results = list(ex.map(worker, ["none", "sc_w16a16", "sc_w8a8"] * 2))
        for name, outs in results:
            for o in outs:
                np.testing.assert_array_equal(o, expected[name], err_msg=name)
        # the three modes genuinely differ (the interleaving proved something)
        assert not np.array_equal(expected["none"], expected["sc_w16a16"])
        assert not np.array_equal(expected["sc_w16a16"], expected["sc_w8a8"])

    def test_two_threads_two_accelerators(self):
        """Full-pipeline variant: float and quantized accelerators served from
        different threads stay bitwise equal to their single-threaded runs."""
        cfg, _, accel_q, params, pts, _ = _smoke_setup(quant="sc_w16a16")
        accel_f = get_accelerator(cfg, ExecutionPolicy(backend="xla"))
        expect = {
            "q": np.asarray(accel_q.infer(params, pts)),
            "f": np.asarray(accel_f.infer(params, pts)),
        }

        def worker(tag):
            accel = accel_q if tag == "q" else accel_f
            return tag, [np.asarray(accel.infer(params, pts)) for _ in range(5)]

        with concurrent.futures.ThreadPoolExecutor(max_workers=2) as ex:
            for tag, outs in ex.map(worker, ["q", "f", "q", "f"]):
                for o in outs:
                    np.testing.assert_array_equal(o, expect[tag], err_msg=tag)
