"""Sharding rules, spec fixing, activation constraints, and cell lowering.

Spec rules are checked on fake meshes of production shape (no devices);
cells are lowered and run on a 1-device mesh against the unsharded steps.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.configs.base import (AttentionConfig, ModelConfig, ShapeConfig,
                                TrainConfig)
from repro.launch import axes
from repro.launch import sharding as sh
from repro.launch import steps as steps_lib
from repro.launch.mesh import batch_axes, make_test_mesh
from repro.models import transformer as T


def tiny_cfg():
    return ModelConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                       d_ff=128, vocab_size=128, compute_dtype="float32",
                       remat_policy="none",
                       attention=AttentionConfig(num_heads=4, num_kv_heads=2,
                                                 head_dim=16))


def assert_trees_close(got, want, rtol=1e-5, atol=1e-6):
    g_leaves, g_def = jax.tree.flatten(got)
    w_leaves, w_def = jax.tree.flatten(want)
    assert g_def == w_def
    for g, w in zip(g_leaves, w_leaves):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=rtol, atol=atol)


class FakeMesh:
    """Mesh stand-in with arbitrary axis sizes (pure dict)."""

    def __init__(self, **axes):
        self.shape = axes
        self.axis_names = tuple(axes)


class TestFixSpec:
    def test_divisible_kept(self):
        mesh = FakeMesh(data=16, model=16)
        spec = sh.fix_spec((32, 4096, 32, 128), (None, "data", "model",
                                                 None), mesh)
        assert tuple(spec) == (None, "data", "model", None)

    def test_kv_heads_relocate_to_head_dim(self):
        mesh = FakeMesh(data=16, model=16)
        spec = sh.fix_spec((32, 4096, 8, 128), (None, "data", "model",
                                                None), mesh)
        assert tuple(spec) == (None, "data", None, "model")

    def test_drop_when_nothing_fits(self):
        mesh = FakeMesh(data=16, model=16)
        spec = sh.fix_spec((3, 5), ("data", "model"), mesh)
        assert tuple(spec) == (None, None)

    def test_batch_axes_tuple(self):
        mesh = FakeMesh(pod=2, data=16, model=16)
        spec = sh.fix_spec((256, 4096), (("pod", "data"), None), mesh)
        assert tuple(spec) == (("pod", "data"), None)

    def test_no_relocation_for_batch(self):
        mesh = FakeMesh(data=16, model=16)
        spec = sh.fix_spec((1, 524288), (("data",), None), mesh,
                           relocate=False)
        assert tuple(spec) == (None, None)


class TestParamSpecs:
    @pytest.mark.parametrize("arch", ["llama3-8b", "qwen2-moe-a2.7b",
                                      "mamba2-370m", "recurrentgemma-9b"])
    def test_every_spec_is_legal(self, arch):
        """On the production mesh shape, every param sharding divides."""
        mesh = FakeMesh(data=16, model=16)
        cfg = registry.get_config(arch)
        shapes = jax.eval_shape(
            functools.partial(T.init_params, cfg=cfg), jax.random.PRNGKey(0))
        specs = sh.param_specs(shapes, mesh)
        for leaf, spec in zip(jax.tree.leaves(shapes),
                              jax.tree.leaves(
                                  specs,
                                  is_leaf=lambda x: isinstance(x, P))):
            for dim, ax in zip(leaf.shape, tuple(spec)):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                div = int(np.prod([mesh.shape[a] for a in axes]))
                assert dim % div == 0, (arch, leaf.shape, tuple(spec))

    def test_big_tensors_are_sharded(self):
        """No multi-GB parameter may end up fully replicated."""
        mesh = FakeMesh(data=16, model=16)
        cfg = registry.get_config("llama4-maverick-400b-a17b")
        shapes = jax.eval_shape(
            functools.partial(T.init_params, cfg=cfg), jax.random.PRNGKey(0))
        specs = sh.param_specs(shapes, mesh)
        for leaf, spec in zip(jax.tree.leaves(shapes),
                              jax.tree.leaves(
                                  specs,
                                  is_leaf=lambda x: isinstance(x, P))):
            nbytes = int(np.prod(leaf.shape)) * 4
            if nbytes > 1 << 30:
                assert any(ax is not None for ax in tuple(spec)), leaf.shape

    def test_memory_estimate_fits_hbm(self):
        """Params + optimizer state per device fit in 16 GB for the 400B
        MoE with Adafactor on the multi-pod mesh (the deployment claim)."""
        from repro.optim.optimizers import make_optimizer

        mesh = FakeMesh(pod=2, data=16, model=16)
        cfg = registry.get_config("llama4-maverick-400b-a17b")
        shapes = jax.eval_shape(
            functools.partial(T.init_params, cfg=cfg), jax.random.PRNGKey(0))
        pspecs = sh.param_specs(shapes, mesh)
        opt = make_optimizer(TrainConfig(optimizer="adafactor"))
        oshapes = jax.eval_shape(opt.init, shapes)
        ospecs = sh.opt_state_specs(oshapes, pspecs, mesh)
        total = (sh.spec_bytes_per_device(shapes, pspecs, mesh)
                 + sh.spec_bytes_per_device(oshapes, ospecs, mesh))
        assert total < 10 * 1024**3, f"{total/1e9:.1f} GB"


class TestCellLowering:
    def test_train_cell_on_2_device_mesh_has_collectives(self):
        """Sharded lowering on a real (1x1) and data=1,model=1 mesh works."""
        cfg = registry.get_smoke_config("llama3-8b")
        mesh = make_test_mesh(1, 1)
        cell = steps_lib.build_cell(cfg, ShapeConfig("t", 32, 2, "train"),
                                    mesh, TrainConfig())
        compiled = cell.lower().compile()
        assert compiled.cost_analysis()["flops"] > 0

    @pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
    def test_cell_kinds(self, kind):
        cfg = registry.get_smoke_config("yi-6b")
        mesh = make_test_mesh(1, 1)
        cell = steps_lib.build_cell(cfg, ShapeConfig("t", 32, 2, kind), mesh,
                                    TrainConfig())
        assert cell.kind == kind
        cell.lower().compile()

    def test_batch_axes(self):
        assert batch_axes(make_test_mesh(1, 1)) == ("data",)

    @pytest.mark.parametrize("optimizer", ["adamw", "adafactor"])
    def test_train_cell_matches_unsharded_step(self, optimizer, rng):
        """The sharded train cell takes the same step as the bare step."""
        cfg, B, S = tiny_cfg(), 2, 32
        tcfg = TrainConfig(optimizer=optimizer, warmup_steps=1)
        cell = steps_lib.build_cell(cfg, ShapeConfig("t", S, B, "train"),
                                    make_test_mesh(1, 1), tcfg)
        step, opt = steps_lib.make_train_step(cfg, tcfg)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        batch = {k: jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                                jnp.int32) for k in ("tokens", "targets")}
        want_p, _, want_m = jax.jit(step)(params, opt.init(params), batch)
        # the cell donates params and optimizer state: give it copies
        got_p, _, got_m = cell.fn(jax.tree.map(jnp.copy, params),
                                  opt.init(params), batch)
        np.testing.assert_allclose(float(got_m["loss"]),
                                   float(want_m["loss"]), rtol=1e-6)
        assert_trees_close(got_p, want_p)

    def test_prefill_cell_matches_model_prefill(self, rng):
        cfg, B, S = tiny_cfg(), 2, 32
        cell = steps_lib.build_cell(cfg, ShapeConfig("t", S, B, "prefill"),
                                    make_test_mesh(1, 1))
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                           jnp.int32)
        got_logits, got_caches = cell.fn(params, {"tokens": toks})
        want_logits, want_caches = jax.jit(
            lambda p, t: T.prefill(p, t, cfg, max_len=S))(params, toks)
        assert_trees_close(got_logits, want_logits)
        assert_trees_close(got_caches, want_caches)

    def test_decode_cell_matches_model_decode_step(self, rng):
        cfg, B, S, P0 = tiny_cfg(), 2, 32, 12
        cell = steps_lib.build_cell(cfg, ShapeConfig("t", S, B, "decode"),
                                    make_test_mesh(1, 1))
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, P0 + 1)),
                           jnp.int32)
        _, caches = T.prefill(params, toks[:, :P0], cfg, max_len=S)
        token, pos = toks[:, P0:], jnp.int32(P0)
        logits, next_token, _ = cell.fn(
            params, {"token": token, "pos": pos, "caches": caches})
        want, _ = jax.jit(lambda p, t, c, i: T.decode_step(p, t, c, i, cfg))(
            params, token, caches, pos)
        assert_trees_close(logits, want)
        np.testing.assert_array_equal(np.asarray(next_token),
                                      np.argmax(np.asarray(want), axis=-1))


class TestAxes:
    def test_constrain_is_identity_without_mesh(self):
        assert axes.current_mesh() is None
        x = jnp.ones((4, 8))
        assert axes.constrain(x, "batch", "tp") is x

    def test_mesh_context_restores_previous_mesh_on_error(self):
        outer, inner = make_test_mesh(1, 1), make_test_mesh(1, 1, pod=1)
        with axes.mesh_context(outer):
            with pytest.raises(RuntimeError):
                with axes.mesh_context(inner):
                    assert axes.current_mesh() is inner
                    raise RuntimeError("body failed")
            assert axes.current_mesh() is outer
        assert axes.current_mesh() is None


class TestBatchAndOptStateSpecs:
    def test_batch_specs_shard_dim0_over_pod_and_data(self):
        mesh = FakeMesh(pod=2, data=16, model=16)
        shapes = {"tokens": jax.ShapeDtypeStruct((64, 4096), jnp.int32),
                  "pos": jax.ShapeDtypeStruct((), jnp.int32)}
        specs = sh.batch_specs(shapes, mesh)
        assert tuple(specs["tokens"]) == (("pod", "data"), None)
        assert tuple(specs["pos"]) == ()

    def test_adafactor_factored_states_drop_reduced_dim(self):
        from repro.optim.optimizers import make_optimizer

        mesh = FakeMesh(data=16, model=16)
        shapes = {"w_gate": jax.ShapeDtypeStruct((4096, 14336),
                                                 jnp.float32)}
        pspecs = sh.param_specs(shapes, mesh)
        assert tuple(pspecs["w_gate"]) == ("data", "model")
        opt = make_optimizer(TrainConfig(optimizer="adafactor"))
        ospecs = sh.opt_state_specs(jax.eval_shape(opt.init, shapes),
                                    pspecs, mesh)
        # vr averages over the last dim, vc over the second-to-last
        assert tuple(ospecs["v"]["w_gate"]["vr"]) == ("data",)
        assert tuple(ospecs["v"]["w_gate"]["vc"]) == ("model",)
