"""Polynomial code (MDS) properties: any-k decoding, exactness, erasures."""

import itertools

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import hypothesis, st

from repro.core import coding
from repro.core.layered_matmul import GradientCoder, LayeredCodedMatmul


class TestModMatmul:
    def test_matches_python_ints(self, rng):
        p = coding.MERSENNE_P
        x = rng.integers(0, p, size=(8, 5), dtype=np.uint64)
        y = rng.integers(0, p, size=(8, 4), dtype=np.uint64)
        got = coding.modmatmul(x, y, p)
        want = np.zeros((5, 4), dtype=object)
        for i in range(5):
            for j in range(4):
                want[i, j] = sum(int(x[k, i]) * int(y[k, j])
                                 for k in range(8)) % p
        assert (got.astype(object) == want).all()


class TestPolynomialCodeFloat:
    @pytest.mark.parametrize("n1,n2,omega", [(2, 2, 1.0), (2, 2, 1.5),
                                             (4, 2, 1.25), (3, 3, 1.2)])
    def test_any_k_subset_decodes(self, rng, n1, n2, omega):
        code = coding.PolynomialCode(n1=n1, n2=n2, omega=omega, mode="float")
        A = jnp.asarray(rng.normal(size=(32, 4 * n1)), jnp.float32)
        B = jnp.asarray(rng.normal(size=(32, 4 * n2)), jnp.float32)
        X, Y = code.encode(A, B)
        assert X.shape[0] == code.num_tasks
        tasks = np.asarray(code.compute_all_tasks(X, Y))
        exact = np.asarray(A.T @ B)
        # try several k-subsets including adversarial (first k, last k)
        ids_list = [list(range(code.k)),
                    list(range(code.num_tasks - code.k, code.num_tasks)),
                    list(rng.choice(code.num_tasks, code.k, replace=False))]
        for ids in ids_list:
            dec = np.asarray(code.decode(ids, tasks[np.asarray(ids)]))
            np.testing.assert_allclose(dec, exact, rtol=2e-2, atol=5e-3)

    @pytest.mark.parametrize("n,K,width", [(2, 7, 3), (8, 70, 4096),
                                           (3, 5, 1)])
    def test_host_encode_matches_einsum(self, rng, n, K, width):
        """The slab-wise BLAS encode equals the plain einsum encode; the
        (8, 70, 4096) case spans three row slabs, the last one partial.
        Tolerance: sums of n float64 products, a few ulps of the largest
        term."""
        code = coding.PolynomialCode(n1=n, n2=n, omega=1.5, mode="float")
        va, vb = coding._encode_basis(code)
        a = rng.integers(-127, 128, size=(K, n * width)).astype(np.int16)
        for got, basis in ((code.encode_a(a), va), (code.encode_b(a), vb)):
            want = np.einsum("skn,st->tkn",
                             code._split(a, n).astype(np.float64), basis)
            assert got.shape == want.shape == (code.num_tasks, K, width)
            scale = 127 * np.abs(basis).sum(axis=0).max()
            np.testing.assert_allclose(got, want, rtol=0,
                                       atol=8 * n * scale * 2.0**-52)

    def test_insufficient_results_raise(self, rng):
        code = coding.PolynomialCode(n1=2, n2=2, omega=1.5)
        with pytest.raises(ValueError):
            code.decode([0, 1], np.zeros((2, 4, 4)))

    def test_redundancy_ratio(self):
        code = coding.PolynomialCode(n1=2, n2=2, omega=1.06)
        assert code.num_tasks == 5  # ceil(4 * 1.06)
        with pytest.raises(ValueError):
            coding.PolynomialCode(n1=2, n2=2, omega=0.9)


class TestPolynomialCodeGFp:
    def test_exact_decode_all_subsets(self, rng):
        code = coding.PolynomialCode(n1=2, n2=1, omega=1.5, mode="gfp")
        A = rng.integers(0, 255, size=(16, 6)).astype(np.uint64)
        B = rng.integers(0, 255, size=(16, 3)).astype(np.uint64)
        X, Y = code.encode(A, B)
        tasks = code.compute_all_tasks(X, Y)
        exact = A.astype(np.int64).T @ B.astype(np.int64)
        for ids in itertools.combinations(range(code.num_tasks), code.k):
            dec = code.decode(list(ids), tasks[np.asarray(ids)])
            np.testing.assert_array_equal(np.asarray(dec), exact)


class TestMDSCode:
    @hypothesis.given(st.integers(2, 6), st.integers(0, 3))
    @hypothesis.settings(max_examples=25, deadline=None)
    def test_erasure_recovery(self, k, extra):
        n = k + extra
        rng = np.random.default_rng(k * 10 + extra)
        mds = coding.MDSCode(k=k, n=n)
        shards = jnp.asarray(rng.normal(size=(k, 6)), jnp.float32)
        cw = mds.encode(shards)
        ids = rng.choice(n, size=k, replace=False)
        rec = mds.decode(ids, cw[jnp.asarray(ids)])
        np.testing.assert_allclose(np.asarray(rec), np.asarray(shards),
                                   rtol=1e-3, atol=1e-4)


class TestLayeredCodedPipeline:
    def test_float_pipeline_resolution_improves(self, rng):
        pipe = LayeredCodedMatmul(m=2, d=8, n1=2, n2=2, omega=1.5)
        A = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
        B = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
        res, _ = pipe.run(A, B, seed=3)
        exact = np.asarray(A.T @ B)
        errs = [np.abs(res[l] - exact).max() for l in range(res.shape[0])]
        assert errs[0] > errs[-1]
        assert errs[-1] < 1e-2 * np.abs(exact).max()

    def test_gfp_pipeline_bit_exact_under_erasure(self, rng):
        pipe = LayeredCodedMatmul(m=2, d=8, n1=2, n2=1, omega=1.5,
                                  mode="gfp")
        A = jnp.asarray(rng.integers(-5000, 5000, size=(32, 4)), jnp.int32)
        B = jnp.asarray(rng.integers(-5000, 5000, size=(32, 4)), jnp.int32)
        res, _ = pipe.run(A, B, erasures=[1])
        exact = np.asarray(A, np.int64).T @ np.asarray(B, np.int64)
        np.testing.assert_array_equal(res[-1].astype(np.int64), exact)

    def test_too_many_erasures_rejected(self, rng):
        pipe = LayeredCodedMatmul(m=2, d=8, n1=2, n2=2, omega=1.0)
        A = jnp.zeros((8, 4), jnp.float32)
        with pytest.raises(ValueError):
            pipe.run(A, A, erasures=[0])


class TestGradientCoder:
    @pytest.mark.parametrize("n,k", [(2, 1), (4, 3), (4, 2), (8, 6)])
    def test_all_survivor_sets_decode(self, rng, n, k):
        gc = GradientCoder(n=n, k=k)
        shards = [jnp.asarray(rng.normal(size=(5,)), jnp.float32)
                  for _ in range(n)]
        cws = [gc.encode_local(p, [shards[s] for s in gc.assignment[p]])
               for p in range(n)]
        total = np.asarray(sum(shards))
        for surv in itertools.combinations(range(n), k):
            dec = gc.decode(list(surv), [cws[s] for s in surv])
            np.testing.assert_allclose(np.asarray(dec), total, rtol=1e-4,
                                       atol=1e-4)

    def test_below_threshold_raises(self):
        gc = GradientCoder(n=4, k=3)
        with pytest.raises(ValueError):
            gc.decode_weights([0, 1])

    def test_replication_factor(self):
        assert GradientCoder(n=8, k=6).replication == 3
        assert GradientCoder(n=4, k=4).replication == 1
