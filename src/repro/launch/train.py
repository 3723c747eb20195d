"""End-to-end training driver.

Runs on anything from this container's single CPU device (quickstart-100m,
smoke configs) to the production mesh (full configs; same code path the
dry-run lowers).  Fault tolerance: async checkpointing every
``--ckpt-every`` steps, SIGTERM -> synchronous final checkpoint, and
``--resume`` restarts from the latest checkpoint — onto a *different* mesh
shape if needed (elastic resume; arrays are stored unsharded and re-placed
with the current sharding rules).

    PYTHONPATH=src python -m repro.launch.train \
        --arch quickstart-100m --steps 300 --batch 8 --seq 256
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import store
from repro.configs import registry
from repro.configs.base import (AttentionConfig, ModelConfig, ShapeConfig,
                                TrainConfig)
from repro.data.pipeline import SyntheticLM
from repro.launch import compile_cache
from repro.launch import sharding as sh
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_test_mesh
from repro.models import transformer as T

__all__ = ["quickstart_100m_config", "train_loop", "main"]


def quickstart_100m_config(vocab: int = 32_768) -> ModelConfig:
    """~100M-param dense LM that trains in minutes on CPU at short seq."""
    return ModelConfig(
        name="quickstart-100m", family="dense", num_layers=12, d_model=768,
        d_ff=3072, vocab_size=vocab,
        attention=AttentionConfig(num_heads=12, num_kv_heads=4, head_dim=64),
        tie_embeddings=True, compute_dtype="float32",
        remat_policy="none")


def _resolve_config(arch: str) -> ModelConfig:
    if arch == "quickstart-100m":
        return quickstart_100m_config()
    if arch.endswith("-smoke"):
        return registry.get_smoke_config(arch[: -len("-smoke")])
    return registry.get_config(arch)


def train_loop(cfg: ModelConfig, tcfg: TrainConfig, *, batch: int, seq: int,
               steps: int, ckpt_dir: str | None = None, ckpt_every: int = 100,
               resume: bool = False, log_every: int = 10,
               mesh=None, seed: int = 0) -> dict:
    mesh = mesh or make_test_mesh(1, 1)
    shape = ShapeConfig("train", seq, batch, "train")
    cell = steps_lib.build_cell(cfg, shape, mesh, tcfg)

    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq,
                       global_batch=batch, seed=seed)
    _, optimizer = steps_lib.make_train_step(cfg, tcfg)

    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    opt_state = optimizer.init(params)
    start_step = 0

    ckpt = None
    if ckpt_dir:
        ckpt = store.AsyncCheckpointer(ckpt_dir)
        latest = store.latest_step(ckpt_dir)
        if resume and latest is not None:
            state = {"params": params, "opt": opt_state}
            pspecs = sh.param_specs(state["params"], mesh)
            shardings = {"params": sh.named(mesh, pspecs),
                         "opt": None}
            state = store.restore(ckpt_dir, latest, state)
            params, opt_state = state["params"], state["opt"]
            start_step = latest
            print(f"[train] resumed from step {latest}")

        def final_save():
            ckpt.wait()
            store.save(ckpt_dir, int(last_step[0]),
                       {"params": params, "opt": opt_state})

        store.install_sigterm_handler(final_save)

    last_step = [start_step]
    losses = []
    t0 = time.time()
    for step in range(start_step, steps):
        b = data.batch_at(step)
        batch_dict = {"tokens": b.tokens, "targets": b.targets}
        if cfg.num_image_tokens:
            batch_dict["extra_embeds"] = jnp.zeros(
                (batch, cfg.num_image_tokens, cfg.d_model), cfg.cdtype())
        if cfg.is_encdec:
            batch_dict["audio_embeds"] = jnp.zeros(
                (batch, cfg.encoder_seq, cfg.d_model), cfg.cdtype())
        params, opt_state, metrics = cell.fn(params, opt_state, batch_dict)
        last_step[0] = step + 1
        if (step + 1) % log_every == 0 or step + 1 == steps:
            loss = float(metrics["loss"])
            losses.append((step + 1, loss))
            rate = (step + 1 - start_step) / (time.time() - t0)
            print(f"[train] step {step + 1:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"({rate:.2f} steps/s)", flush=True)
        if ckpt and (step + 1) % ckpt_every == 0:
            ckpt.save(step + 1, {"params": params, "opt": opt_state})
    if ckpt:
        ckpt.wait()
        store.save(ckpt_dir, steps, {"params": params, "opt": opt_state})
    return {"losses": losses, "params": params, "opt_state": opt_state}


def main(argv=None) -> int:
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="quickstart-100m",
                    help="arch id, '<id>-smoke', or 'quickstart-100m'")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args(argv)

    cfg = _resolve_config(args.arch)
    tcfg = TrainConfig(optimizer=args.optimizer, learning_rate=args.lr,
                       warmup_steps=min(100, args.steps // 10 + 1),
                       total_steps=args.steps)
    out = train_loop(cfg, tcfg, batch=args.batch, seq=args.seq,
                     steps=args.steps, ckpt_dir=args.ckpt_dir,
                     ckpt_every=args.ckpt_every, resume=args.resume)
    first, last = out["losses"][0][1], out["losses"][-1][1]
    print(f"[train] loss {first:.4f} -> {last:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
