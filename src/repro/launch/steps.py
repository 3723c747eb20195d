"""Step functions (train / prefill / serve) and their sharded lowering.

``build_cell`` is the entry point the training driver and the tests share:
given (arch config, shape cell, mesh) it constructs the step function, the
ShapeDtypeStruct inputs, and the in/out shardings, and returns a
``jax.jit``-wrapped callable ready to ``.lower()`` or execute.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import registry
from repro.configs.base import SHAPES, ModelConfig, ShapeConfig, TrainConfig
from repro.launch import sharding as sh
from repro.launch.axes import mesh_context
from repro.models import transformer as T
from repro.optim.optimizers import make_optimizer

__all__ = ["make_train_step", "make_prefill_step", "make_serve_step",
           "build_cell", "Cell"]

_QCHUNK = 1024  # query-block size for chunked attention (prefill & train)


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig):
    optimizer = make_optimizer(tcfg)

    def train_step(params, opt_state, batch):
        def loss_fn(p):
            loss, metrics = T.forward_train(
                p, batch["tokens"], batch["targets"], cfg,
                extra_embeds=batch.get("extra_embeds"),
                audio_embeds=batch.get("audio_embeds"),
                q_chunk=_QCHUNK)
            return loss, metrics

        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        new_params, new_opt = optimizer.update(grads, opt_state, params)
        metrics = dict(metrics)
        metrics["grad_norm"] = new_opt.get("gnorm", jnp.float32(0))
        return new_params, new_opt, metrics

    return train_step, optimizer


def make_prefill_step(cfg: ModelConfig, max_len: int):
    def prefill_step(params, batch):
        return T.prefill(params, batch["tokens"], cfg, max_len=max_len,
                         extra_embeds=batch.get("extra_embeds"),
                         audio_embeds=batch.get("audio_embeds"),
                         q_chunk=_QCHUNK)

    return prefill_step


def make_serve_step(cfg: ModelConfig):
    def serve_step(params, batch):
        logits, caches = T.decode_step(params, batch["token"],
                                       batch["caches"], batch["pos"], cfg)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return logits, next_token, caches

    return serve_step


# ---------------------------------------------------------------------------
# Cell construction (arch x shape x mesh)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    """Everything needed to lower or run one (arch x shape x mesh) cell."""

    cfg: ModelConfig
    shape: ShapeConfig
    mesh: Mesh
    kind: str                    # train | prefill | decode
    fn: Any                      # jitted step function
    arg_shapes: tuple            # ShapeDtypeStructs, positional
    in_shardings: tuple
    out_shardings: Any

    def lower(self):
        return self.fn.lower(*self.arg_shapes)


def _abstract_params(cfg: ModelConfig):
    return jax.eval_shape(
        functools.partial(T.init_params, cfg=cfg), jax.random.PRNGKey(0))


def build_cell(cfg: ModelConfig, shape: ShapeConfig | str, mesh: Mesh,
               tcfg: Optional[TrainConfig] = None) -> Cell:
    if isinstance(shape, str):
        shape = SHAPES[shape]
    kind, batch_shapes = registry.input_specs(cfg, shape)
    params_shapes = _abstract_params(cfg)
    pspecs = sh.param_specs(params_shapes, mesh)

    if kind == "train":
        tcfg = tcfg or TrainConfig()
        step, optimizer = make_train_step(cfg, tcfg)
        opt_shapes = jax.eval_shape(optimizer.init, params_shapes)
        ospecs = sh.opt_state_specs(opt_shapes, pspecs, mesh)
        bspecs = sh.batch_specs(batch_shapes, mesh)
        arg_shapes = (params_shapes, opt_shapes, batch_shapes)
        in_specs = (pspecs, ospecs, bspecs)
        # output opt_state grows scalar entries (gnorm/lr) -> respecify
        out_shapes = jax.eval_shape(step, *arg_shapes)
        out_specs = (pspecs, sh.opt_state_specs(out_shapes[1], pspecs, mesh),
                     jax.tree.map(lambda _: P(), out_shapes[2]))
        donate = (0, 1)
    elif kind == "prefill":
        step = make_prefill_step(cfg, max_len=shape.seq_len)
        bspecs = sh.batch_specs(batch_shapes, mesh)
        arg_shapes = (params_shapes, batch_shapes)
        in_specs = (pspecs, bspecs)
        out_shapes = jax.eval_shape(step, *arg_shapes)
        baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        logits_spec = P(baxes, "model")
        out_specs = (logits_spec, sh.cache_specs_tree(out_shapes[1], mesh))
        donate = ()
    elif kind == "decode":
        step = make_serve_step(cfg)
        cspecs = sh.cache_specs_tree(batch_shapes["caches"], mesh)
        baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        # batch=1 (long_500k) cannot shard over the batch axes: fix_spec
        # drops the axis (single-sequence decode is TP-only, by design)
        tok_spec = sh.fix_spec(batch_shapes["token"].shape, (baxes, None),
                               mesh, relocate=False)
        bspecs = {"token": tok_spec, "pos": P(), "caches": cspecs}
        arg_shapes = (params_shapes, batch_shapes)
        in_specs = (pspecs, bspecs)
        out_shapes = jax.eval_shape(step, *arg_shapes)
        B, V = out_shapes[0].shape
        logits_spec = sh.fix_spec((B, V), (baxes, "model"), mesh,
                                  relocate=False)
        next_spec = sh.fix_spec((B,), (baxes,), mesh, relocate=False)
        out_specs = (logits_spec, next_spec, cspecs)
        donate = ()   # caches donated at run time; lowering keeps both
    else:
        raise ValueError(kind)

    named_in = sh.named(mesh, in_specs)
    named_out = sh.named(mesh, out_specs)

    def step_in_mesh(*args, _step=step):
        # activation sharding constraints (launch/axes.py) need the ambient
        # mesh DURING tracing, which happens lazily inside jit
        with mesh_context(mesh):
            return _step(*args)

    jitted = jax.jit(step_in_mesh, in_shardings=named_in,
                     out_shardings=named_out, donate_argnums=donate)
    return Cell(cfg=cfg, shape=shape, mesh=mesh, kind=kind, fn=jitted,
                arg_shapes=arg_shapes, in_shardings=named_in,
                out_shardings=named_out)
