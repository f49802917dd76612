"""Per-kernel allclose tests: Pallas (interpret=True) vs pure-jnp oracles,
swept over shapes/metrics/dtypes as required for each kernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fps.ops import fps_tiles
from repro.kernels.fps.ref import fps_tiles_ref
from repro.kernels.knn3.ops import knn3
from repro.kernels.knn3.ref import knn3_ref
from repro.kernels.lattice.ops import lattice_query_fused
from repro.kernels.sc_matmul.ops import sc_matmul_op, sc_quantized_linear
from repro.kernels.sc_matmul.ref import sc_matmul_ref
from repro.core.query import lattice_query

jax.config.update("jax_platform_name", "cpu")


def _cloud(shape, seed=0, dtype=jnp.float32):
    return jax.random.uniform(
        jax.random.PRNGKey(seed), shape, minval=-1.0, maxval=1.0
    ).astype(dtype)


class TestFPSKernel:
    @pytest.mark.parametrize("metric", ["l1", "l2"])
    @pytest.mark.parametrize("t,p,k", [(1, 128, 8), (4, 256, 16), (2, 512, 32)])
    def test_matches_oracle(self, metric, t, p, k):
        pts = _cloud((t, p, 3), seed=t * 100 + k)
        got = np.array(fps_tiles(pts, k, metric=metric, backend="pallas", interpret=True))
        ref = np.array(fps_tiles_ref(pts.transpose(0, 2, 1), k, metric=metric))
        np.testing.assert_array_equal(got, ref)

    def test_non_lane_multiple_padding(self):
        pts = _cloud((3, 200, 3), seed=7)
        got = np.array(fps_tiles(pts, 12, backend="pallas", interpret=True))
        ref = np.array(fps_tiles(pts, 12, backend="xla"))
        np.testing.assert_array_equal(got, ref)

    def test_indices_unique_per_tile(self):
        pts = _cloud((2, 256, 3), seed=9)
        idx = np.array(fps_tiles(pts, 32, backend="pallas", interpret=True))
        for row in idx:
            assert len(np.unique(row)) == 32

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtype_sweep(self, dtype):
        pts = _cloud((2, 128, 3), seed=3, dtype=dtype)
        got = np.array(fps_tiles(pts, 8, backend="pallas", interpret=True))
        ref = np.array(
            fps_tiles_ref(pts.astype(jnp.float32).transpose(0, 2, 1), 8, metric="l1")
        )
        np.testing.assert_array_equal(got, ref)


class TestSCMatmulKernel:
    # (192, 259, 256): cls global MLP at max_batch=3 — M padded to 256 rows
    @pytest.mark.parametrize(
        "m,k,n", [(8, 64, 16), (32, 128, 32), (128, 512, 128), (192, 259, 256)]
    )
    def test_exact_vs_f32_oracle(self, m, k, n):
        x = jax.random.randint(jax.random.PRNGKey(0), (m, k), -32768, 32768, jnp.int32)
        w = jax.random.randint(jax.random.PRNGKey(1), (k, n), -32768, 32768, jnp.int32)
        got = np.array(sc_matmul_op(x, w, backend="pallas", interpret=True))
        oracle = np.array(sc_matmul_ref(x, w))
        np.testing.assert_array_equal(got, oracle)  # identical schedule -> bitwise

    def test_multi_k_step_accumulation(self):
        x = jax.random.randint(jax.random.PRNGKey(2), (128, 1024), -32768, 32768, jnp.int32)
        w = jax.random.randint(jax.random.PRNGKey(3), (1024, 128), -32768, 32768, jnp.int32)
        got = np.array(sc_matmul_op(x, w, backend="pallas", interpret=True))
        ref = np.array(x, np.int64) @ np.array(w, np.int64)
        rel = np.abs(got - ref).max() / np.abs(ref).max()
        assert rel < 1e-6  # f32 combine rounding only

    @pytest.mark.parametrize("bits", [8, 16])
    def test_bits_sweep_small_exact(self, bits):
        lim = 1 << (bits - 1)
        x = jax.random.randint(jax.random.PRNGKey(4), (16, 64), -lim, lim, jnp.int32)
        w = jax.random.randint(jax.random.PRNGKey(5), (64, 16), -lim, lim, jnp.int32)
        got = np.array(sc_matmul_op(x, w, bits=bits, backend="pallas", interpret=True))
        ref = np.array(x, np.int64) @ np.array(w, np.int64)
        if bits == 8:  # fits f32 exactly
            np.testing.assert_array_equal(got, ref)
        else:
            assert np.abs(got - ref).max() / np.abs(ref).max() < 1e-6

    def test_quantized_linear_accuracy(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (32, 128))
        w = jax.random.normal(jax.random.PRNGKey(1), (128, 64)) * 0.05
        y = sc_quantized_linear(x, w, backend="pallas", interpret=True)
        ref = x @ w
        rel = float(jnp.linalg.norm(y - ref) / jnp.linalg.norm(ref))
        assert rel < 3e-4  # 16-bit PTQ bound (paper Fig 12a)


class TestKNN3Kernel:
    @pytest.mark.parametrize("metric", ["l1", "l2"])
    @pytest.mark.parametrize("q,p", [(8, 128), (64, 256), (100, 200)])
    def test_matches_oracle(self, metric, q, p):
        qs = _cloud((q, 3), seed=q)
        pts = _cloud((p, 3), seed=p + 1)
        gi, gd = knn3(qs, pts, metric=metric, backend="pallas", interpret=True)
        ri, rd = knn3_ref(qs, pts.T, metric=metric)
        np.testing.assert_array_equal(np.array(gi), np.array(ri))
        np.testing.assert_allclose(np.array(gd), np.array(rd), rtol=1e-5)

    def test_k_sweep(self):
        qs, pts = _cloud((16, 3), 1), _cloud((128, 3), 2)
        for k in [1, 3, 5]:
            gi, _ = knn3(qs, pts, k=k, backend="pallas", interpret=True)
            ri, _ = knn3_ref(qs, pts.T, k=k)
            np.testing.assert_array_equal(np.array(gi), np.array(ri))

    @pytest.mark.parametrize(
        "q,p", [(1, 100), (5, 130), (7, 128), (13, 257), (261, 129), (300, 640)]
    )
    def test_odd_shapes_match_oracle(self, q, p):
        # regression: Q not a multiple of the sublane (8) used to require the
        # op wrapper to guess a divisible block; the kernel now pads queries
        # internally, so arbitrary Q/P go straight through
        qs = _cloud((q, 3), seed=q)
        pts = _cloud((p, 3), seed=p + 1)
        gi, gd = knn3(qs, pts, backend="pallas", interpret=True)
        ri, rd = knn3_ref(qs, pts.T)
        assert gi.shape == (q, 3) and gd.shape == (q, 3)
        np.testing.assert_array_equal(np.array(gi), np.array(ri))
        np.testing.assert_allclose(np.array(gd), np.array(rd), rtol=1e-5)

    def test_direct_kernel_bq_larger_than_q(self):
        # regression: bq > qn after clamping (the default bq=256 with a tiny
        # odd Q) must sublane-align and pad instead of failing the divisibility
        # check — and give the same answer as a fitted block
        from repro.kernels.knn3.kernel import knn3_pallas

        qs = _cloud((5, 3), seed=3)
        pts = _cloud((128, 3), seed=4).T
        i_default, d_default = knn3_pallas(qs, pts, bq=256, interpret=True)
        i_fit, d_fit = knn3_pallas(qs, pts, bq=8, interpret=True)
        assert i_default.shape == (5, 3)
        np.testing.assert_array_equal(np.array(i_default), np.array(i_fit))
        np.testing.assert_array_equal(np.array(d_default), np.array(d_fit))


class TestLatticeKernel:
    @pytest.mark.parametrize("m,p,ns", [(4, 128, 8), (16, 256, 16), (128, 512, 32)])
    def test_matches_oracle(self, m, p, ns):
        pts = _cloud((p, 3), seed=p)
        c = pts[:m]
        got = lattice_query_fused(pts, c, 0.4, ns, backend="pallas", interpret=True)
        ref = lattice_query(pts, c, 0.4, ns)
        np.testing.assert_array_equal(np.array(got.mask), np.array(ref.mask))
        np.testing.assert_array_equal(np.array(got.idx), np.array(ref.idx))

    def test_non_multiple_shapes(self):
        pts = _cloud((200, 3), seed=11)
        c = pts[:50]
        got = lattice_query_fused(pts, c, 0.5, 8, backend="pallas", interpret=True)
        ref = lattice_query(pts, c, 0.5, 8)
        np.testing.assert_array_equal(np.array(got.mask), np.array(ref.mask))
        np.testing.assert_array_equal(np.array(got.idx), np.array(ref.idx))
