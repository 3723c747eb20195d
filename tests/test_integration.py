"""Integration tests: end-to-end training learns; the sharded train cell
runs; the int8 KV cache; train-loop checkpoint/resume."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.configs.base import (AttentionConfig, ModelConfig, ShapeConfig,
                                TrainConfig)
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_test_mesh
from repro.launch.train import train_loop
from repro.models import transformer as T


def tiny_cfg(**kw):
    base = dict(name="tiny", family="dense", num_layers=2, d_model=64,
                d_ff=128, vocab_size=128, compute_dtype="float32",
                remat_policy="none", tie_embeddings=True,
                attention=AttentionConfig(num_heads=4, num_kv_heads=2,
                                          head_dim=16))
    base.update(kw)
    return ModelConfig(**base)


class TestTrainingLearns:
    def test_loss_decreases_on_bigram_chain(self):
        cfg = tiny_cfg(vocab_size=32)   # small table -> learns in ~100 steps
        tcfg = TrainConfig(learning_rate=5e-3, warmup_steps=5,
                           total_steps=100, weight_decay=0.0)
        out = train_loop(cfg, tcfg, batch=4, seq=64, steps=100,
                         log_every=20)
        first, last = out["losses"][0][1], out["losses"][-1][1]
        # vocab ceiling ln(32) ~ 3.47; chain entropy ln(8) ~ 2.08
        assert last < first - 0.3, (first, last)

    def test_checkpoint_resume_continues(self, tmp_path):
        cfg = tiny_cfg()
        tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=2,
                           total_steps=20)
        train_loop(cfg, tcfg, batch=2, seq=32, steps=10,
                   ckpt_dir=str(tmp_path), ckpt_every=5, log_every=5)
        out = train_loop(cfg, tcfg, batch=2, seq=32, steps=20,
                         ckpt_dir=str(tmp_path), resume=True, log_every=5)
        assert out["losses"][0][0] > 10  # resumed past step 10


class TestShardingProfiles:
    def test_profiles_lower_and_run(self):
        cfg = tiny_cfg()
        mesh = make_test_mesh(1, 1)
        cell = steps_lib.build_cell(cfg, ShapeConfig("t", 32, 2, "train"),
                                    mesh, TrainConfig())
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        _, opt = steps_lib.make_train_step(cfg, TrainConfig())
        state = opt.init(params)
        batch = {"tokens": jnp.zeros((2, 32), jnp.int32),
                 "targets": jnp.zeros((2, 32), jnp.int32)}
        p2, s2, m = cell.fn(params, state, batch)
        assert np.isfinite(float(m["loss"]))


class TestInt8KVCache:
    def test_decode_consistency_within_quant_error(self, rng):
        cfg = tiny_cfg(kv_cache_dtype="int8")
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        toks = jnp.asarray(rng.integers(0, 128, (2, 17)), jnp.int32)
        ref_cfg = dataclasses.replace(cfg, kv_cache_dtype="")
        full, _ = T.forward(params, toks, ref_cfg)
        want = np.asarray(full[:, -1, :], np.float32)
        _, cache = T.prefill(params, toks[:, :16], cfg, max_len=24)
        got, _ = T.decode_step(params, toks[:, 16:17], cache,
                               jnp.int32(16), cfg)
        err = (np.abs(np.asarray(got, np.float32) - want).max()
               / np.abs(want).max())
        assert err < 0.1, err

    def test_cache_is_actually_int8(self):
        cfg = tiny_cfg(kv_cache_dtype="int8")
        caches = T.init_cache(cfg, 2, 16)
        leaves = jax.tree.leaves(caches)
        assert any(x.dtype == jnp.int8 for x in leaves)

    def test_hybrid_int8_window_cache(self, rng):
        from repro.configs.base import RGLRUConfig
        cfg = tiny_cfg(family="hybrid", num_layers=3,
                       rglru=RGLRUConfig(d_rnn=64, window=8),
                       attention=AttentionConfig(num_heads=4,
                                                 num_kv_heads=1,
                                                 head_dim=16),
                       kv_cache_dtype="int8")
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        toks = jnp.asarray(rng.integers(0, 128, (2, 16)), jnp.int32)
        _, cache = T.prefill(params, toks, cfg, max_len=24)
        logits, _ = T.decode_step(params, toks[:, -1:], cache,
                                  jnp.int32(16), cfg)
        assert np.isfinite(np.asarray(logits, np.float32)).all()


class TestMoEDispatchGroup:
    def test_group_size_is_semantically_neutral(self, rng):
        """Changing dispatch_group (the A1 perf knob) must not change the
        routed outputs when capacity is ample."""
        from repro.configs.base import MoEConfig
        from repro.models import moe as moe_lib

        cfg_a = MoEConfig(num_experts=4, top_k=2, d_ff_expert=16,
                          capacity_factor=8.0, dispatch_group=4096)
        cfg_b = dataclasses.replace(cfg_a, dispatch_group=8)
        params = moe_lib.init_moe_params(jax.random.PRNGKey(1), 8, cfg_a,
                                         jnp.float32)
        x = jnp.asarray(rng.normal(size=(32, 8)), jnp.float32)
        ya = np.asarray(moe_lib.moe_block(params, x, cfg_a))
        yb = np.asarray(moe_lib.moe_block(params, x, cfg_b))
        np.testing.assert_allclose(ya, yb, rtol=2e-4, atol=2e-4)
