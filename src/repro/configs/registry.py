"""Architecture registry: ``--arch <id>`` resolution + per-cell input specs.

``input_specs(cfg, shape)`` returns ``(kind, specs)`` where ``specs`` is a
dict of ``jax.ShapeDtypeStruct`` stand-ins for every input of the step
function that the cell lowers — weak-type-correct and shardable, with **no
device allocation** (the full configs are only ever exercised through
``.lower()``; real arrays exist only for smoke/reduced configs).
"""

from __future__ import annotations

import importlib
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, ModelConfig, ShapeConfig

__all__ = ["ARCH_IDS", "get_config", "get_smoke_config", "shape_cells",
           "input_specs", "cache_specs"]

ARCH_IDS: dict[str, str] = {
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
    "llama4-maverick-400b-a17b": "llama4_maverick_400b_a17b",
    "internvl2-1b": "internvl2_1b",
    "mamba2-370m": "mamba2_370m",
    "llama3-8b": "llama3_8b",
    "yi-6b": "yi_6b",
    "glm4-9b": "glm4_9b",
    "starcoder2-7b": "starcoder2_7b",
    "whisper-tiny": "whisper_tiny",
    "recurrentgemma-9b": "recurrentgemma_9b",
    "deepseek-v2-lite": "deepseek_v2_lite",
}


def _module(arch: str):
    if arch not in ARCH_IDS:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(ARCH_IDS)}")
    return importlib.import_module(f"repro.configs.{ARCH_IDS[arch]}")


def get_config(arch: str) -> ModelConfig:
    return _module(arch).CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    return _module(arch).smoke_config()


def shape_cells(cfg: ModelConfig) -> list[str]:
    """Applicable input-shape cells for this architecture.

    ``long_500k`` needs sub-quadratic sequence mixing — skipped for pure
    full-attention archs.  All ten archs
    bear a decoder, so decode shapes always apply.
    """
    cells = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.sub_quadratic:
        cells.append("long_500k")
    return cells


def _sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def cache_specs(cfg: ModelConfig, batch: int, max_len: int):
    """ShapeDtypeStructs for the decode caches (no allocation)."""
    from repro.models import transformer as T

    caches = jax.eval_shape(
        lambda: T.init_cache(cfg, batch, max_len))
    if not cfg.is_encdec:
        return caches

    def enc_kv_shapes():
        dt = cfg.cdtype()
        a = cfg.attention
        kvs = []
        from repro.models.transformer import block_groups
        for (unit, reps) in block_groups(cfg):
            for _ in unit:
                shp = (reps, batch, cfg.encoder_seq, a.num_kv_heads,
                       a.head_dim)
                kvs.append((jnp.zeros(shp, dt), jnp.zeros(shp, dt)))
        return kvs

    enc_kvs = jax.eval_shape(enc_kv_shapes)
    return (caches, enc_kvs)


def input_specs(cfg: ModelConfig, shape: ShapeConfig | str) -> tuple[str, dict]:
    """(kind, specs) for the step function this (arch x shape) cell lowers.

    kind == "train":   train_step(params, opt_state, batch) — specs = batch
    kind == "prefill": prefill_step(params, batch)
    kind == "decode":  serve_step(params, batch) with KV/state caches inside
    """
    if isinstance(shape, str):
        shape = SHAPES[shape]
    B, S = shape.global_batch, shape.seq_len
    specs: dict[str, Any] = {}

    if shape.kind in ("train", "prefill"):
        specs["tokens"] = _sds((B, S), jnp.int32)
        if shape.kind == "train":
            specs["targets"] = _sds((B, S), jnp.int32)
        if cfg.num_image_tokens:
            specs["extra_embeds"] = _sds(
                (B, cfg.num_image_tokens, cfg.d_model), cfg.cdtype())
        if cfg.is_encdec:
            specs["audio_embeds"] = _sds(
                (B, cfg.encoder_seq, cfg.d_model), cfg.cdtype())
        return shape.kind, specs

    # decode: one new token against caches of length S
    specs["token"] = _sds((B, 1), jnp.int32)
    specs["pos"] = _sds((), jnp.int32)
    specs["caches"] = cache_specs(cfg, B, S)
    return "decode", specs
