"""Tests for core/quant.py (C4 — split-concatenate exact integer MACs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis import given, settings, st

from repro.core import quant as QT

jax.config.update("jax_platform_name", "cpu")


def _randint16(shape, seed):
    return np.array(
        jax.random.randint(jax.random.PRNGKey(seed), shape, -32768, 32768, dtype=jnp.int32)
    )


class TestPlaneSplit:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_roundtrip(self, seed):
        q = jnp.array(_randint16((64,), seed))
        planes = QT.split_planes(q)
        assert planes.shape == (4, 64)
        # low planes unsigned nibbles; top plane signed
        p = np.array(planes)
        assert (p[:3] >= 0).all() and (p[:3] <= 15).all()
        assert (p[3] >= -8).all() and (p[3] <= 7).all()
        np.testing.assert_array_equal(np.array(QT.combine_planes(planes)), np.array(q))

    def test_negative_edge_cases(self):
        q = jnp.array([-32768, -1, 0, 1, 32767, -4096, 4095], jnp.int32)
        np.testing.assert_array_equal(
            np.array(QT.combine_planes(QT.split_planes(q))), np.array(q)
        )


class TestSCMatmul:
    @pytest.mark.parametrize("m,k,n", [(4, 8, 4), (16, 32, 8), (1, 128, 16)])
    def test_exact_int64(self, m, k, n):
        x = _randint16((m, k), 0)
        w = _randint16((k, n), 1)
        with jax.enable_x64():
            got = np.array(QT.sc_matmul(jnp.array(x), jnp.array(w), combine="int64"))
        ref = x.astype(np.int64) @ w.astype(np.int64)
        np.testing.assert_array_equal(got, ref)

    def test_f32_combine_close(self):
        x = _randint16((8, 64), 2)
        w = _randint16((64, 8), 3)
        got = np.array(QT.sc_matmul(jnp.array(x), jnp.array(w), combine="f32"))
        ref = (x.astype(np.int64) @ w.astype(np.int64)).astype(np.float64)
        np.testing.assert_allclose(got, ref, rtol=1e-6)

    def test_plane_dots_fit_int32(self):
        # worst case magnitudes: |plane| <= 15 -> |dot| <= 225*K
        k = 4096
        assert 225 * k < 2**31


class TestQuantizedLinear:
    def test_w16a16_accuracy(self):
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (32, 64))
        w = jax.random.normal(jax.random.PRNGKey(1), (64, 32)) * 0.1
        y = QT.quantized_linear(x, w, bits=16)
        ref = x @ w
        rel = float(jnp.linalg.norm(y - ref) / jnp.linalg.norm(ref))
        assert rel < 3e-4  # paper: 16-bit PTQ <0.3% accuracy effect

    def test_w8a8_coarser(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 32))
        w = jax.random.normal(jax.random.PRNGKey(1), (32, 16)) * 0.1
        y = QT.quantized_linear(x, w, bits=8)
        ref = x @ w
        rel = float(jnp.linalg.norm(y - ref) / jnp.linalg.norm(ref))
        assert rel < 2e-2

    def test_ptq_error_bound(self):
        x = jax.random.normal(jax.random.PRNGKey(5), (1024,))
        assert float(QT.ptq_error(x, 16)) < 3e-4


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 100_000), k=st.integers(1, 64))
def test_property_sc_matmul_exact(seed, k):
    """Property: plane-decomposed matmul is EXACTLY the int matmul, any shapes/values."""
    key = jax.random.PRNGKey(seed)
    x = jax.random.randint(key, (3, k), -32768, 32768, dtype=jnp.int32)
    w = jax.random.randint(jax.random.PRNGKey(seed + 1), (k, 5), -32768, 32768, dtype=jnp.int32)
    with jax.enable_x64():
        got = np.array(QT.sc_matmul(x, w, combine="int64"))
    ref = np.array(x, np.int64) @ np.array(w, np.int64)
    np.testing.assert_array_equal(got, ref)


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 100_000))
def test_property_plane_split_roundtrip(seed):
    q = jax.random.randint(jax.random.PRNGKey(seed), (17,), -32768, 32768, dtype=jnp.int32)
    np.testing.assert_array_equal(
        np.array(QT.combine_planes(QT.split_planes(q))), np.array(q)
    )
