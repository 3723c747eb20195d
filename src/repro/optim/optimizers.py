"""Optimizers: AdamW and Adafactor, with schedule + global-norm clipping.

Self-contained (no optax in this environment).  Both optimizers follow the
``init(params) -> state`` / ``update(grads, state, params) -> (params',
state')`` interface and keep fp32 master weights regardless of the compute
dtype; the 400B llama4 config defaults to Adafactor so the optimizer state
fits the single-pod memory budget (``test_memory_estimate_fits_hbm``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import TrainConfig

__all__ = ["make_optimizer", "Optimizer", "cosine_schedule", "global_norm"]


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def clip_by_global_norm(tree, max_norm: float):
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda x: (x.astype(jnp.float32) * scale), tree), norm


def cosine_schedule(cfg: TrainConfig) -> Callable[[jax.Array], jax.Array]:
    def lr(step):
        step = step.astype(jnp.float32)
        warm = cfg.learning_rate * step / max(cfg.warmup_steps, 1)
        t = jnp.clip((step - cfg.warmup_steps)
                     / max(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
        cos = 0.5 * cfg.learning_rate * (1.0 + jnp.cos(np.pi * t))
        return jnp.where(step < cfg.warmup_steps, warm, cos)
    return lr


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable[[Any], Any]
    update: Callable[[Any, Any, Any], tuple[Any, Any]]
    # state pytree structure mirrors params; scalars live in state["_"]


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------

def adamw(cfg: TrainConfig) -> Optimizer:
    lr_fn = cosine_schedule(cfg)

    def init(params):
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        # gnorm/lr live in the state so init and update return IDENTICAL
        # pytree structures (jit in_shardings are structure-keyed)
        return {
            "step": jnp.zeros((), jnp.int32),
            "m": jax.tree.map(zeros, params),
            "v": jax.tree.map(zeros, params),
            "gnorm": jnp.zeros((), jnp.float32),
            "lr": jnp.zeros((), jnp.float32),
        }

    def update(grads, state, params):
        step = state["step"] + 1
        grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
        b1, b2 = cfg.b1, cfg.b2
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g,
                         state["m"], grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(g),
                         state["v"], grads)
        bc1 = 1.0 - b1 ** step.astype(jnp.float32)
        bc2 = 1.0 - b2 ** step.astype(jnp.float32)
        lr = lr_fn(step)

        def upd(p, m_, v_):
            mhat = m_ / bc1
            vhat = v_ / bc2
            delta = mhat / (jnp.sqrt(vhat) + 1e-8)
            delta = delta + cfg.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * delta).astype(p.dtype)

        new_params = jax.tree.map(upd, params, m, v)
        return new_params, {"step": step, "m": m, "v": v,
                            "gnorm": gnorm, "lr": lr}

    return Optimizer(init=init, update=update)


# ---------------------------------------------------------------------------
# Adafactor (factored second moments; the 400B-scale default)
# ---------------------------------------------------------------------------

def _factored(shape) -> bool:
    return len(shape) >= 2 and shape[-1] >= 8 and shape[-2] >= 8


def adafactor(cfg: TrainConfig) -> Optimizer:
    lr_fn = cosine_schedule(cfg)
    eps = 1e-30

    def init(params):
        def per_leaf(p):
            if _factored(p.shape):
                return {
                    "vr": jnp.zeros(p.shape[:-1], jnp.float32),
                    "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:],
                                    jnp.float32),
                }
            return {"v": jnp.zeros(p.shape, jnp.float32)}
        return {"step": jnp.zeros((), jnp.int32),
                "v": jax.tree.map(per_leaf, params,
                                  is_leaf=lambda x: hasattr(x, "shape")),
                "gnorm": jnp.zeros((), jnp.float32),
                "lr": jnp.zeros((), jnp.float32)}

    def update(grads, state, params):
        step = state["step"] + 1
        grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
        t = step.astype(jnp.float32)
        beta2 = 1.0 - t ** (-0.8)          # Adafactor decay schedule
        lr = lr_fn(step)

        def upd(p, g, v):
            g2 = jnp.square(g) + eps
            if _factored(p.shape):
                vr = beta2 * v["vr"] + (1 - beta2) * g2.mean(-1)
                vc = beta2 * v["vc"] + (1 - beta2) * g2.mean(-2)
                denom = (vr[..., None] * vc[..., None, :]
                         / jnp.maximum(vr.mean(-1, keepdims=True)[..., None],
                                       eps))
                pre = g * jax.lax.rsqrt(denom + eps)
                nv = {"vr": vr, "vc": vc}
            else:
                nv = {"v": beta2 * v["v"] + (1 - beta2) * g2}
                pre = g * jax.lax.rsqrt(nv["v"] + eps)
            # update clipping (Adafactor's d=1.0 RMS clip)
            rms = jnp.sqrt(jnp.mean(jnp.square(pre)) + eps)
            pre = pre / jnp.maximum(1.0, rms)
            delta = pre + cfg.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), nv

        flat_p, tdef = jax.tree.flatten(params)
        flat_g = tdef.flatten_up_to(grads)
        flat_v = tdef.flatten_up_to(state["v"])
        out = [upd(p, g, v) for p, g, v in zip(flat_p, flat_g, flat_v)]
        new_params = tdef.unflatten([o[0] for o in out])
        new_v = tdef.unflatten([o[1] for o in out])
        return new_params, {"step": step, "v": new_v,
                            "gnorm": gnorm, "lr": lr}

    return Optimizer(init=init, update=update)


def make_optimizer(cfg: TrainConfig) -> Optimizer:
    if cfg.optimizer == "adamw":
        return adamw(cfg)
    if cfg.optimizer == "adafactor":
        return adafactor(cfg)
    raise ValueError(f"unknown optimizer {cfg.optimizer!r}")
