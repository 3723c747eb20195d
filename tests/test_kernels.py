"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp/NumPy oracles.

All kernels run in interpret mode on CPU (the TPU BlockSpecs execute as
Python), matching the brief's validation recipe.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import hypothesis, st

from repro.core import layering
from repro.kernels import ops, ref


class TestLayeredMatmulKernel:
    @pytest.mark.parametrize("m,d,K,M,N", [
        (2, 7, 64, 16, 24),
        (2, 7, 1024, 128, 128),   # multi-block K accumulation
        (3, 5, 128, 128, 128),
        (4, 4, 32, 8, 8),
        (1, 7, 16, 8, 8),         # degenerate single layer
    ])
    def test_partials_exact(self, rng, m, d, K, M, N):
        hi = 1 << (m * d - 1)
        A = jnp.asarray(rng.integers(-hi, hi, size=(K, M)), jnp.int32)
        B = jnp.asarray(rng.integers(-hi, hi, size=(K, N)), jnp.int32)
        parts = np.asarray(ops.layered_matmul_partials(A, B, m=m, d=d,
                                                       interpret=True))
        pa = np.asarray(layering.decompose(A, m, d), np.int64)
        pb = np.asarray(layering.decompose(B, m, d), np.int64)
        L = 2 * m - 1
        want = np.stack([
            sum(pa[i].T @ pb[j]
                for (i, j) in layering.layer_minijobs(m, l))
            for l in range(L)])
        np.testing.assert_array_equal(parts, want)

    def test_host_fusion_bit_exact(self, rng):
        m, d, K = 2, 7, 256
        hi = 1 << (m * d - 1)
        A = jnp.asarray(rng.integers(-hi, hi, size=(K, 16)), jnp.int32)
        B = jnp.asarray(rng.integers(-hi, hi, size=(K, 16)), jnp.int32)
        parts = np.asarray(ops.layered_matmul_partials(A, B, m=m, d=d,
                                                       interpret=True),
                           np.int64)
        scales = np.asarray([1 << ((2 * m - 2 - l) * d)
                             for l in range(2 * m - 1)], np.int64)
        recon = (parts * scales[:, None, None]).cumsum(0)[-1]
        exact = np.asarray(A, np.int64).T @ np.asarray(B, np.int64)
        np.testing.assert_array_equal(recon, exact)

    def test_fused_wrapper_matches_oracle(self, rng):
        m, d = 2, 6
        hi = 1 << (m * d - 1)
        A = jnp.asarray(rng.integers(-hi, hi, size=(64, 32)), jnp.int32)
        B = jnp.asarray(rng.integers(-hi, hi, size=(64, 8)), jnp.int32)
        got = np.asarray(ops.layered_matmul(A, B, m=m, d=d, interpret=True))
        want = ref.layered_matmul_ref(
            np.asarray(layering.decompose(A, m, d)),
            np.asarray(layering.decompose(B, m, d)), d=d)
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_resolution_monotone_improvement(self, rng):
        m, d = 3, 4
        A = jnp.asarray(rng.integers(0, 1 << (m * d - 1), size=(32, 16)),
                        jnp.int32)
        B = jnp.asarray(rng.integers(0, 1 << (m * d - 1), size=(32, 16)),
                        jnp.int32)
        res = np.asarray(ops.layered_matmul(A, B, m=m, d=d, interpret=True))
        exact = np.asarray(A, np.int64).T @ np.asarray(B, np.int64)
        errs = [np.abs(res[l] - exact).max() for l in range(res.shape[0])]
        assert all(a >= b for a, b in zip(errs, errs[1:]))

    def test_d_too_large_rejected(self):
        with pytest.raises(ValueError):
            ops.layered_matmul(jnp.zeros((8, 8), jnp.int32),
                               jnp.zeros((8, 8), jnp.int32), m=2, d=8,
                               interpret=True)

    def test_compiled_by_default_no_cpu_fallback(self):
        """Without ``interpret=True`` the kernel compiles for the TPU; on
        the CPU that fails instead of silently interpreting."""
        with pytest.raises(ValueError, match="interpret"):
            ops.layered_matmul_partials(jnp.zeros((512, 128), jnp.int32),
                                        jnp.zeros((512, 128), jnp.int32))


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("B,S,H,kv,dh,causal,window,dtype", [
        (2, 128, 4, 2, 64, True, None, jnp.float32),
        (1, 256, 2, 1, 32, True, 64, jnp.float32),
        (2, 64, 4, 4, 16, False, None, jnp.float32),
        (1, 512, 2, 2, 128, True, None, jnp.float32),
        (1, 128, 2, 2, 64, True, None, jnp.bfloat16),
    ])
    def test_matches_reference(self, rng, B, S, H, kv, dh, causal, window,
                               dtype):
        q = jnp.asarray(rng.normal(size=(B, S, H, dh)), dtype)
        k = jnp.asarray(rng.normal(size=(B, S, kv, dh)), dtype)
        v = jnp.asarray(rng.normal(size=(B, S, kv, dh)), dtype)
        got = np.asarray(ops.flash_attention(q, k, v, causal=causal,
                                             window=window, interpret=True),
                         np.float32)
        G = H // kv
        qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, dh)
        kf = jnp.broadcast_to(k.transpose(0, 2, 1, 3)[:, :, None],
                              (B, kv, G, S, dh)).reshape(B * H, S, dh)
        vf = jnp.broadcast_to(v.transpose(0, 2, 1, 3)[:, :, None],
                              (B, kv, G, S, dh)).reshape(B * H, S, dh)
        want = np.asarray(ref.flash_attention_ref(qf, kf, vf, causal=causal,
                                                  window=window), np.float32)
        want = want.reshape(B, H, S, dh).transpose(0, 2, 1, 3)
        tol = 2e-2 if dtype == jnp.bfloat16 else 3e-5
        np.testing.assert_allclose(got, want, atol=tol, rtol=tol)

    def test_matches_model_attention_layer(self, rng):
        """Kernel agrees with the jnp attention used by the models."""
        from repro.configs.base import AttentionConfig
        from repro.models.layers import attention

        B, S, H, kv, dh = 2, 128, 4, 2, 32
        cfg = AttentionConfig(num_heads=H, num_kv_heads=kv, head_dim=dh)
        q = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, kv, dh)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, kv, dh)), jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        want = np.asarray(attention(q, k, v, pos, pos, cfg))
        got = np.asarray(ops.flash_attention(q, k, v, causal=True,
                                             interpret=True))
        np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)

    @hypothesis.given(st.integers(1, 3), st.sampled_from([64, 128, 256]),
                      st.sampled_from([16, 32, 64]))
    @hypothesis.settings(max_examples=8, deadline=None)
    def test_property_rows_are_convex_combinations(self, B, S, dh):
        rng = np.random.default_rng(S + dh)
        q = jnp.asarray(rng.normal(size=(B, S, 2, dh)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, 2, dh)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, 2, dh)), jnp.float32)
        out = np.asarray(ops.flash_attention(q, k, v, causal=True,
                                             interpret=True))
        # every output is a convex combination of values -> bounded by V
        vmax = np.abs(np.asarray(v)).max()
        assert np.abs(out).max() <= vmax + 1e-4


class TestSSDScanKernel:
    @pytest.mark.parametrize("B,S,H,P,N,chunk", [
        (2, 48, 4, 8, 16, 16),
        (1, 64, 2, 16, 32, 32),
        (1, 32, 8, 8, 8, 8),
    ])
    def test_matches_jnp_ssd(self, rng, B, S, H, P, N, chunk):
        from repro.kernels.ops import ssd_scan_fused
        from repro.models.ssm import ssd_scan

        x = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
        dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(B, S, H)),
                         jnp.float32)
        A = -jnp.asarray(rng.uniform(0.5, 2.0, size=(H,)), jnp.float32)
        Bm = jnp.asarray(rng.normal(size=(B, S, 1, N)), jnp.float32)
        Cm = jnp.asarray(rng.normal(size=(B, S, 1, N)), jnp.float32)
        got_y, got_s = ssd_scan_fused(x, dt, A, Bm, Cm, chunk=chunk,
                                      interpret=True)
        want_y, want_s = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
        np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                                   rtol=1e-4, atol=1e-4)

    def test_state_carries_across_chunks(self, rng):
        """One long scan == same scan with 4x more chunks (state carried)."""
        from repro.kernels.ops import ssd_scan_fused

        B, S, H, P, N = 1, 64, 2, 8, 8
        x = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
        dt = jnp.asarray(rng.uniform(0.01, 0.1, size=(B, S, H)), jnp.float32)
        A = -jnp.ones((H,), jnp.float32)
        Bm = jnp.asarray(rng.normal(size=(B, S, 1, N)), jnp.float32)
        Cm = jnp.asarray(rng.normal(size=(B, S, 1, N)), jnp.float32)
        y1, s1 = ssd_scan_fused(x, dt, A, Bm, Cm, chunk=64, interpret=True)
        y2, s2 = ssd_scan_fused(x, dt, A, Bm, Cm, chunk=16, interpret=True)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   rtol=1e-4, atol=1e-4)
