"""Unit + property tests for the paper's core layering math (Definition 1)."""

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import hypothesis, st

from repro.core import layering


class TestBookkeeping:
    @pytest.mark.parametrize("m", [1, 2, 3, 4, 7])
    def test_minijob_count_sums_to_m_squared(self, m):
        # sum_l J(l) = m^2 -- layering adds zero total compute (paper §III)
        assert sum(layering.minijobs_per_layer(m)) == m * m

    @pytest.mark.parametrize("m", [1, 2, 3, 5])
    def test_J_formula(self, m):
        for l in range(layering.num_layers(m)):
            want = min(l + 1, 2 * m - 1 - l)
            assert layering.minijobs_per_layer(m)[l] == want
            assert len(layering.layer_minijobs(m, l)) == want

    @pytest.mark.parametrize("m", [2, 3, 4])
    def test_layers_partition_all_plane_pairs(self, m):
        seen = set()
        for l in range(layering.num_layers(m)):
            for (i, j) in layering.layer_minijobs(m, l):
                assert (2 * m - 2) - l == i + j
                seen.add((i, j))
        assert seen == {(i, j) for i in range(m) for j in range(m)}

    def test_msb_first_order(self):
        order = layering.all_minijobs_msb_first(3)
        sums = [i + j for (_, i, j) in order]
        assert sums == sorted(sums, reverse=True)

    def test_bad_args(self):
        with pytest.raises(ValueError):
            layering.num_layers(0)
        with pytest.raises(ValueError):
            layering.layer_minijobs(2, 5)


class TestDecompose:
    @hypothesis.given(
        st.integers(min_value=1, max_value=4),
        st.integers(min_value=1, max_value=8),
        st.integers(min_value=0, max_value=2**31 - 1),
    )
    @hypothesis.settings(max_examples=100, deadline=None)
    def test_roundtrip_scalar(self, m, d, value):
        # keep value within m*d bits so the decomposition is exhaustive
        value = value % (2 ** min(m * d, 31))
        x = jnp.asarray([[value]], jnp.int32)
        ch = layering.decompose(x, m, d)
        assert int(layering.reconstruct(ch, d)[0, 0]) == value

    @hypothesis.given(st.integers(min_value=-2**15, max_value=2**15 - 1))
    @hypothesis.settings(max_examples=60, deadline=None)
    def test_roundtrip_signed(self, value):
        x = jnp.asarray([[value]], jnp.int32)
        for (m, d) in [(2, 8), (4, 4), (2, 10)]:
            ch = layering.decompose(x, m, d)
            assert int(layering.reconstruct(ch, d)[0, 0]) == value, (m, d)

    def test_roundtrip_array(self, rng):
        x = jnp.asarray(rng.integers(-2**20, 2**20, size=(33, 17)), jnp.int32)
        ch = layering.decompose(x, 3, 8)
        assert ch.shape == (3, 33, 17)
        np.testing.assert_array_equal(np.asarray(layering.reconstruct(ch, 8)),
                                      np.asarray(x))

    def test_lower_chunks_are_digits(self, rng):
        x = jnp.asarray(rng.integers(-2**15, 2**15, size=(8, 8)), jnp.int32)
        ch = np.asarray(layering.decompose(x, 2, 8))
        assert ch[0].min() >= 0 and ch[0].max() < 256

    def test_rejects_floats(self):
        with pytest.raises(TypeError):
            layering.decompose(jnp.zeros((2, 2), jnp.float32), 2, 8)


class TestLayeredMatmul:
    @pytest.mark.parametrize("m,d", [(2, 8), (3, 6), (4, 4)])
    def test_final_resolution_exact(self, rng, m, d):
        hi = 1 << (m * d - 1)
        A = rng.integers(-hi, hi, size=(24, 9))
        B = rng.integers(-hi, hi, size=(24, 7))
        res = layering.layered_matmul_reference(A, B, m=m, d=d)
        assert res.shape == (2 * m - 1, 9, 7)
        np.testing.assert_array_equal(res[-1], A.T @ B)

    def test_resolution_error_decreases(self, rng):
        m, d = 3, 6
        A = rng.integers(0, 1 << (m * d), size=(32, 8))
        B = rng.integers(0, 1 << (m * d), size=(32, 8))
        res = layering.layered_matmul_reference(A, B, m=m, d=d)
        exact = (A.T @ B).astype(np.float64)
        errs = [np.abs(res[l] - exact).max() for l in range(res.shape[0])]
        assert all(e1 >= e2 for e1, e2 in zip(errs, errs[1:])), errs
        assert errs[-1] == 0

    def test_error_bound_holds(self, rng):
        m, d, K = 2, 8, 16
        A = rng.integers(0, 1 << (m * d), size=(K, 6))
        B = rng.integers(0, 1 << (m * d), size=(K, 6))
        res = layering.layered_matmul_reference(A, B, m=m, d=d)
        exact = A.T @ B
        for l in range(2 * m - 1):
            bound = layering.resolution_error_bound(m, d, K, l)
            assert np.abs(res[l] - exact).max() <= bound

    @pytest.mark.parametrize("bits", [7, 20, 24, 28])
    def test_exact_int_matmul_matches_int64(self, rng, bits):
        """Exact on both sides of the float64 threshold: K * 2^(2*bits)
        is below 2**53 for 7 and 20 bits (BLAS path), above it for 24
        and 28 (int64 path; still no int64 overflow)."""
        K = 64
        A = rng.integers(-(1 << bits), 1 << bits, size=(K, 5))
        B = rng.integers(-(1 << bits), 1 << bits, size=(K, 7))
        got = layering.exact_int_matmul(A, B)
        assert got.dtype == np.int64
        np.testing.assert_array_equal(got, A.T @ B)

    def test_exact_int_matmul_wide_int16(self, rng):
        """Operands wider than one float64 slab, in their own int16 type:
        every column slab lands in place."""
        K = 64
        N = 2 * layering.HOST_SLAB_ELEMS // K + 3
        A = rng.integers(-(1 << 12), 1 << 12, size=(K, 3)).astype(np.int16)
        B = rng.integers(-(1 << 12), 1 << 12, size=(K, N)).astype(np.int16)
        got = layering.exact_int_matmul(A, B)
        np.testing.assert_array_equal(
            got, A.T.astype(np.int64) @ B.astype(np.int64))

    @pytest.mark.parametrize("m,d", [(1, 8), (2, 7), (3, 6)])
    def test_planes_reference_matches_minijob_loop(self, rng, m, d):
        """The per-B-plane products equal the plain loop over each layer's
        mini-jobs, exactly."""
        hi = 1 << (m * d - 1)
        A = rng.integers(-hi, hi, size=(24, 5)).astype(np.int16)
        B = rng.integers(-hi, hi, size=(24, 9))
        ca = layering._np_decompose(A, m, d)
        cb = layering._np_decompose(B, m, d)
        assert ca.dtype == np.int16 and cb.dtype == np.int64
        want, acc = [], np.zeros((5, 9), np.int64)
        for l in range(layering.num_layers(m)):
            for (i, j) in layering.layer_minijobs(m, l):
                acc = acc + (ca[i].T.astype(np.int64) @ cb[j]
                             ) * (1 << ((i + j) * d))
            want.append(acc)
        got = layering.layered_planes_reference(ca, cb, d=d)
        np.testing.assert_array_equal(got, np.stack(want))
        np.testing.assert_array_equal(got[-1], A.T.astype(np.int64) @ B)

    def test_jnp_path_matches_reference(self, rng):
        m, d = 2, 7
        hi = 1 << (m * d - 1)
        A = jnp.asarray(rng.integers(-hi, hi, size=(16, 8)), jnp.int32)
        B = jnp.asarray(rng.integers(-hi, hi, size=(16, 4)), jnp.int32)
        got = np.asarray(layering.layered_matmul_jnp(A, B, m=m, d=d))
        want = layering.layered_matmul_reference(np.asarray(A),
                                                 np.asarray(B), m=m, d=d)
        np.testing.assert_allclose(got, want.astype(np.float64), rtol=1e-6)


class TestQuantize:
    @hypothesis.given(st.integers(min_value=4, max_value=16))
    @hypothesis.settings(max_examples=20, deadline=None)
    def test_quantize_bounds(self, bits):
        rng = np.random.default_rng(bits)
        x = jnp.asarray(rng.normal(size=(16, 16)), jnp.float32)
        q, scale = layering.quantize(x, bits)
        qmax = 2 ** (bits - 1) - 1
        assert int(jnp.abs(q).max()) <= qmax
        rel = float(jnp.abs(q * scale - x).max())
        assert rel <= float(scale) * 0.5 + 1e-6
