"""DecodePlan: cached-operator decode equivalence, LRU behavior, reuse.

The property test (hypothesis-optional via ``_hypothesis_compat``) checks
the ISSUE's contract: decoding through the cached plan is *bit-identical*
to the uncached path (operator rebuilt fresh, same arithmetic) for random
arrival-ID subsets, in both float and gfp modes.
"""

import numpy as np
import pytest
from _hypothesis_compat import hypothesis, st

from repro.core import coding


def _task_results(code, rng, K=32, mb=4, nb=4):
    """Encode a random job and compute every coded task's result."""
    if code.mode == "float":
        a = rng.integers(0, 255, size=(K, mb * code.n1)).astype(np.float64)
        b = rng.integers(0, 255, size=(K, nb * code.n2)).astype(np.float64)
        X, Y = code.encode(a, b)
        tasks = np.stack([X[t].T @ Y[t] for t in range(code.num_tasks)])
    else:
        a = rng.integers(0, 255, size=(K, mb * code.n1)).astype(np.uint64)
        b = rng.integers(0, 255, size=(K, nb * code.n2)).astype(np.uint64)
        X, Y = code.encode(a, b)
        tasks = code.compute_all_tasks(X, Y)
    return a, b, tasks


class TestDecodePlanEquivalence:
    @pytest.mark.parametrize("mode", ["float", "gfp"])
    @hypothesis.given(seed=st.integers(0, 2**32 - 1))
    @hypothesis.settings(max_examples=20, deadline=None)
    def test_cached_decode_bit_identical_to_uncached(self, mode, seed):
        rng = np.random.default_rng(seed)
        code = coding.PolynomialCode(n1=2, n2=2, omega=1.5, mode=mode)
        _, _, tasks = _task_results(code, rng)
        ids = rng.permutation(code.num_tasks)[: code.k]
        plan = coding.DecodePlan(code.points(), code.k, mode=mode)
        res = tasks[np.asarray(ids)]
        cached = plan.solve(list(ids), res)               # populates cache
        cached2 = plan.solve(list(ids), res)              # cache hit
        uncached = plan.solve(list(ids), res, use_cache=False)
        np.testing.assert_array_equal(cached, uncached)
        np.testing.assert_array_equal(cached, cached2)
        assert plan.hits >= 1

    @pytest.mark.parametrize("mode", ["float", "gfp"])
    def test_plan_decode_matches_exact_product(self, mode):
        rng = np.random.default_rng(7)
        code = coding.PolynomialCode(n1=2, n2=2, omega=1.5, mode=mode)
        a, b, tasks = _task_results(code, rng)
        exact = a.astype(np.int64).T @ b.astype(np.int64)
        for trial in range(5):
            ids = rng.permutation(code.num_tasks)[: code.k]
            dec = np.asarray(code.decode(list(ids), tasks[np.asarray(ids)]))
            if mode == "gfp":
                np.testing.assert_array_equal(dec.astype(np.int64), exact)
            else:
                np.testing.assert_allclose(dec, exact, rtol=1e-9, atol=1e-6)

    def test_arrival_order_canonicalized(self):
        """Permuted arrivals of the same ID set are one cache entry and
        decode to the same coefficients."""
        rng = np.random.default_rng(3)
        code = coding.PolynomialCode(n1=2, n2=2, omega=1.5)
        _, _, tasks = _task_results(code, rng)
        plan = coding.DecodePlan(code.points(), code.k)
        ids = [4, 1, 5, 2]
        out1 = plan.solve(ids, tasks[np.asarray(ids)])
        perm = [5, 2, 4, 1]
        out2 = plan.solve(perm, tasks[np.asarray(perm)])
        np.testing.assert_allclose(out1, out2, rtol=1e-12, atol=1e-12)
        info = plan.cache_info()
        assert info["misses"] == 1 and info["hits"] == 1


class TestDecodePlanCache:
    def test_lru_eviction(self):
        """With cache_size=2 a third distinct ID set evicts the least
        recently used entry; revisiting it is a fresh miss."""
        code = coding.PolynomialCode(n1=2, n2=1, omega=2.0)  # k=2, T=4
        plan = coding.DecodePlan(code.points(), code.k, cache_size=2)
        res = np.zeros((2, 3, 3))
        plan.solve([0, 1], res)          # miss: {0,1}
        plan.solve([0, 2], res)          # miss: {0,2}
        plan.solve([0, 1], res)          # hit, refreshes {0,1}
        plan.solve([0, 3], res)          # miss, evicts LRU {0,2}
        info = plan.cache_info()
        assert info == {"hits": 1, "misses": 3, "evictions": 1,
                        "currsize": 2, "maxsize": 2}
        plan.solve([0, 2], res)          # evicted -> miss again
        assert plan.cache_info()["misses"] == 4

    def test_code_plan_is_shared_per_geometry(self):
        c1 = coding.PolynomialCode(n1=2, n2=2, omega=1.5)
        c2 = coding.PolynomialCode(n1=2, n2=2, omega=1.5)
        c3 = coding.PolynomialCode(n1=2, n2=2, omega=2.0)
        assert c1.plan() is c2.plan()
        assert c1.plan() is not c3.plan()

    def test_mds_decode_stays_jit_traceable(self):
        """JAX codewords take the device path: decode composes with
        jax.jit (ids static), as before the plan refactor."""
        import jax
        import jax.numpy as jnp

        rng = np.random.default_rng(1)
        mds = coding.MDSCode(k=3, n=5)
        shards = jnp.asarray(rng.normal(size=(3, 6)), jnp.float32)
        cw = mds.encode(shards)
        ids = (3, 0, 4)
        fn = jax.jit(lambda c: mds.decode(ids, c))
        rec = fn(cw[jnp.asarray(ids)])
        np.testing.assert_allclose(np.asarray(rec), np.asarray(shards),
                                   rtol=1e-3, atol=1e-4)

    def test_mds_decode_through_plan(self):
        rng = np.random.default_rng(0)
        # plans are shared process-wide by geometry: one that no other
        # test decodes (test_coding's MDS cases stop at n = k + 3), so
        # the first decode of these ids is a miss whatever ran before
        mds = coding.MDSCode(k=3, n=11)
        shards = rng.normal(size=(3, 6)).astype(np.float32)
        cw = np.asarray(mds.encode(shards))
        before = mds.plan().cache_info()["misses"]
        ids = [10, 0, 2]
        rec = np.asarray(mds.decode(ids, cw[np.asarray(ids)]))
        np.testing.assert_allclose(rec, shards, rtol=1e-3, atol=1e-4)
        assert mds.plan().cache_info()["misses"] == before + 1
        mds.decode(ids, cw[np.asarray(ids)])
        assert mds.plan().cache_info()["misses"] == before + 1  # cache hit
