"""Progressive (layered) serving: LayeredLinear, resolution series, the
deadline-bounded server, and the layered gradient all-reduce."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AttentionConfig, ModelConfig
from repro.core import progressive
from repro.launch.serve import ProgressiveServer
from repro.models import transformer as T
from repro.optim import layered_grads


class TestLayeredLinear:
    def test_full_resolution_equals_quantized_product(self, rng):
        W = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
        x = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
        ll = progressive.make_layered_linear(W, m=3, d=5)
        full = progressive.layered_linear_apply(ll, x)
        # error bounded by quantization, not layering
        err = float(jnp.abs(full - x @ W).max())
        assert err < 0.05 * float(jnp.abs(x @ W).max()) + 1e-3

    def test_series_monotone_and_last_equals_full(self, rng):
        W = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
        x = jnp.asarray(rng.normal(size=(3, 16)), jnp.float32)
        ll = progressive.make_layered_linear(W, m=4, d=4)
        series = progressive.resolution_series(ll, x)
        assert series.shape[0] == 4
        full = x @ W
        errs = [float(jnp.abs(series[l] - full).max()) for l in range(4)]
        assert all(a >= b for a, b in zip(errs, errs[1:])), errs
        np.testing.assert_allclose(
            np.asarray(series[-1]),
            np.asarray(progressive.layered_linear_apply(ll, x)), rtol=1e-5)

    def test_two_sided_layering_num_layers(self, rng):
        x = jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)
        W = jnp.asarray(rng.normal(size=(8, 6)), jnp.float32)
        out = progressive.two_sided_layered_matmul(x, W, m=3, d=5)
        assert out.shape == (5, 3, 6)  # L = 2m-1
        errs = [float(jnp.abs(out[l] - x @ W).max()) for l in range(5)]
        assert errs[0] >= errs[-1]

    def test_resolution_out_of_range(self, rng):
        ll = progressive.make_layered_linear(jnp.eye(4), m=2, d=4)
        with pytest.raises(ValueError):
            progressive.layered_linear_apply(ll, jnp.ones((1, 4)),
                                             resolution=5)


class TestProgressiveServer:
    def _setup(self, rng):
        cfg = ModelConfig(
            name="t", family="dense", num_layers=2, d_model=32, d_ff=64,
            vocab_size=128, compute_dtype="float32",
            attention=AttentionConfig(num_heads=2, num_kv_heads=1,
                                      head_dim=16))
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        server = ProgressiveServer(cfg, params, m=3, d=5)
        toks = jnp.asarray(rng.integers(0, 128, (2, 8)), jnp.int32)
        return cfg, params, server, toks

    def test_full_budget_matches_reference_decode(self, rng):
        cfg, params, server, toks = self._setup(rng)
        _, caches = server.prefill(toks, max_len=16)
        out, stats = server.decode(toks[:, -1:], caches, 8, 4)
        assert out.shape == (2, 4)
        assert stats.full_resolution == stats.steps == 4
        # compare against plain greedy decode (within quantization slack:
        # argmax can differ only when top-2 logits are within quant error)
        _, caches2 = T.prefill(params, toks, cfg, max_len=16)
        tok = toks[:, -1:]
        agree = 0
        for i in range(4):
            logits, caches2 = T.decode_step(params, tok, caches2,
                                            jnp.int32(8 + i), cfg)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            agree += int((np.asarray(tok[:, 0]) ==
                          np.asarray(out[:, i])).mean() == 1.0)
        assert agree >= 3  # near-perfect agreement at full resolution

    def test_budget_one_still_generates(self, rng):
        cfg, params, server, toks = self._setup(rng)
        _, caches = server.prefill(toks, max_len=16)
        out, stats = server.decode(toks[:, -1:], caches, 8, 4,
                                   layer_budget=1)
        assert out.shape == (2, 4)
        assert stats.full_resolution == 0
        assert all(r == 1 for r in stats.released_at_layer)

    def test_deadline_ms_bounds_compute(self, rng):
        """The wall-clock deadline path runs each head step as a runtime
        job: an already-expired deadline releases ONLY the guaranteed
        resolution-0 minimum, and a generous one reaches the full
        ``L = 2m - 1`` layered resolution and agrees with the
        non-deadline decode (up to two-sided quantization)."""
        cfg, params, server, toks = self._setup(rng)
        with server:
            _, caches = server.prefill(toks, max_len=16)
            out, stats = server.decode(toks[:, -1:], caches, 8, 4,
                                       deadline_ms=0.0)
            assert out.shape == (2, 4)
            assert stats.resolutions == 2 * server.m - 1
            assert stats.released_at_layer == [1] * 4
            assert stats.full_resolution == 0
            assert len(stats.head_service_seconds) == 4

            _, caches = server.prefill(toks, max_len=16)
            out_full, stats_full = server.decode(toks[:, -1:], caches, 8, 4,
                                                 deadline_ms=1e9)
            assert (stats_full.released_at_layer
                    == [2 * server.m - 1] * 4)
            assert stats_full.full_resolution == 4
            _, caches = server.prefill(toks, max_len=16)
            out_ref, _ = server.decode(toks[:, -1:], caches, 8, 4)
            # the runtime head decomposes BOTH operands (the reference
            # path only layers W), so argmax can drift on near-ties:
            # demand near-perfect agreement, not identity
            agree = int((np.asarray(out_full)
                         == np.asarray(out_ref)).mean() * 8)
            assert agree >= 6, (np.asarray(out_full), np.asarray(out_ref))

    def test_deeper_budget_closer_to_full(self, rng):
        """Fraction of tokens agreeing with the full-resolution decode
        increases with the layer budget (the paper's quality/deadline
        trade-off, on-chip)."""
        cfg, params, server, toks = self._setup(rng)
        _, c0 = server.prefill(toks, max_len=32)
        full, _ = server.decode(toks[:, -1:], c0, 8, 8)
        agreements = []
        for budget in (1, 2, 3):
            _, c = server.prefill(toks, max_len=32)
            out, _ = server.decode(toks[:, -1:], c, 8, 8,
                                   layer_budget=budget)
            agreements.append(
                float((np.asarray(out) == np.asarray(full)).mean()))
        assert agreements[-1] >= agreements[0]


class TestLayeredGradAllreduce:
    def test_plane_roundtrip(self, rng):
        g = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
        planes, scale = layered_grads.plane_split(g, m=3, d=5)
        rec = layered_grads.plane_reconstruct(planes, scale, d=5)
        assert float(jnp.abs(rec - g).max()) < float(scale) + 1e-6

    def test_partial_reconstruction_monotone(self, rng):
        g = jnp.asarray(rng.normal(size=(16,)), jnp.float32)
        planes, scale = layered_grads.plane_split(g, m=4, d=4)
        errs = []
        for l in range(4):
            rec = layered_grads.plane_reconstruct(planes, scale, d=4,
                                                  up_to_plane=l)
            errs.append(float(jnp.abs(rec - g).max()))
        assert all(a >= b for a, b in zip(errs, errs[1:])), errs

    def test_single_device_allreduce_tree(self, rng):
        """On a 1-device mesh the layered mean == the gradient itself."""
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh(1, 1)
        g = {"w": jnp.asarray(rng.normal(size=(1, 8, 8)), jnp.float32)}
        out = layered_grads.layered_allreduce_tree(g, mesh, "data", m=2,
                                                   d=8)
        err = float(jnp.abs(out["w"] - g["w"]).max())
        scale = float(jnp.abs(g["w"]).max()) / (2**15 - 1)
        assert err <= scale * 2

    def test_layered_psum_emits_per_plane_collectives(self, rng):
        """The traced program issues one psum per plane (the layered
        collective schedule the paper's deadline semantics need).  On a
        1-device mesh XLA elides the wire op, so we check the jaxpr."""
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh(1, 1)
        m = 3

        def fn(planes):
            return jax.shard_map(
                lambda p: layered_grads.layered_psum(p, "data"),
                mesh=mesh, in_specs=P(None, "data"),
                out_specs=P(None, "data"))(planes)

        jaxpr = str(jax.make_jaxpr(fn)(
            jnp.zeros((m, 4, 4), jnp.float32)))
        assert jaxpr.count("psum") >= m
