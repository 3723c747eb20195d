"""Decoder-LM assembly: pattern-grouped scan-over-layers, train/prefill/decode.

An architecture is a sequence of *block groups*: each group is a repeating
unit of layer kinds (e.g. ``("dense",) x 32`` for llama3,
``("dense", "moe") x 24`` for llama4's interleaved MoE,
``("rglru", "rglru", "local_attn") x 12 + ("rglru", "rglru") x 1`` for
recurrentgemma).  Per-group parameters are stacked on a leading ``repeats``
axis and the group runs under ``jax.lax.scan`` (+ configurable
``jax.checkpoint``), keeping HLO size O(1) in depth and bounding live
activations — required for the 48L/400B dry-run cells to compile quickly
and fit.

Layer kinds:
  dense       GQA attention + (SwiGLU | GELU) MLP
  moe         GQA attention + routed-experts FFN (repro.models.moe)
  ssm         Mamba2 SSD block (repro.models.ssm)
  rglru       RG-LRU recurrent block + MLP (repro.models.rglru)
  local_attn  sliding-window GQA + MLP (recurrentgemma's attention layers)
  cross       encoder-decoder layer: causal self-attn + cross-attn + MLP

With ``attention.kv_lora_rank`` set, the attention of dense and moe
layers is latent attention (DeepSeek-V2's MLA): the cache holds one
``(B, max_len, kv_lora_rank + qk_rope_head_dim)`` latent per layer in
place of per-head K/V, prefill expands it and decode absorbs the
up-projection (``models/layers.py``).  ``moe.first_dense_layers`` puts a
group of dense layers before the MoE group.

Caches: every kind owns a cache pytree stacked like its params; decode
scans over (params, cache) pairs and emits updated caches.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.launch.axes import constrain
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import ssm as ssm_lib

__all__ = [
    "block_groups", "init_params", "forward_train", "prefill",
    "decode_layers", "decode_step", "init_cache", "count_params",
    "active_params",
]

# Static KV-cache quantization scale (int8 mode).  Keys/values are
# post-RoPE bf16 activations with |x| <~ 4 for RMS-normed streams; a static
# scale keeps the cache layout trivially shardable.  A production system
# would calibrate per-head scales; the decode-consistency test bounds the
# logit error this introduces.
_KV_SCALE = 24.0


def _cache_dtype(cfg: ModelConfig):
    return jnp.int8 if cfg.kv_cache_dtype == "int8" else cfg.cdtype()


def _quant_kv(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.kv_cache_dtype != "int8":
        return x
    return jnp.clip(jnp.round(x.astype(jnp.float32) * _KV_SCALE),
                    -127, 127).astype(jnp.int8)


def _dequant_kv(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if x.dtype != jnp.int8:
        return x
    return (x.astype(cfg.cdtype()) / jnp.asarray(_KV_SCALE, cfg.cdtype()))


# ---------------------------------------------------------------------------
# Architecture pattern
# ---------------------------------------------------------------------------

def block_groups(cfg: ModelConfig) -> list[tuple[tuple[str, ...], int]]:
    """[(unit kinds, repeats)] covering cfg.num_layers exactly."""
    Lnum = cfg.num_layers
    if cfg.family == "ssm":
        return [(("ssm",), Lnum)]
    if cfg.family == "hybrid":
        unit = tuple("rglru" if c == "R" else "local_attn"
                     for c in cfg.rglru.block_pattern)
        reps, rem = divmod(Lnum, len(unit))
        groups = [(unit, reps)] if reps else []
        if rem:
            groups.append((unit[:rem], 1))
        return groups
    if cfg.family == "moe" and cfg.moe.first_dense_layers:
        n = cfg.moe.first_dense_layers
        assert cfg.moe.interleave_step == 1 and 0 < n < Lnum, (n, Lnum)
        return [(("dense",), n), (("moe",), Lnum - n)]
    if cfg.family == "moe" and cfg.moe.interleave_step > 1:
        step = cfg.moe.interleave_step
        assert Lnum % step == 0, (Lnum, step)
        unit = tuple("dense" if i < step - 1 else "moe" for i in range(step))
        return [(unit, Lnum // step)]
    if cfg.family == "moe":
        return [(("moe",), Lnum)]
    if cfg.is_encdec:
        return [(("cross",), Lnum)]
    return [(("dense",), Lnum)]


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def _init_attn(key, cfg: ModelConfig, reps: tuple[int, ...]) -> dict:
    a = cfg.attention
    ks = jax.random.split(key, 4)
    dt = cfg.pdtype()
    D = cfg.d_model
    if a.latent:
        H, r = a.num_heads, a.kv_lora_rank
        nope, rope, dv = a.qk_nope_head_dim, a.qk_rope_head_dim, a.v_head_dim
        return {
            "wq": L.init_linear(ks[0], D, H * (nope + rope), dt, reps
                                ).reshape(reps + (D, H, nope + rope)),
            "wkv_a": L.init_linear(ks[1], D, r + rope, dt, reps),
            "kv_norm": {"scale": jnp.zeros(reps + (r,), dt)},
            "wkv_b": L.init_linear(ks[2], r, H * (nope + dv), dt, reps
                                   ).reshape(reps + (r, H, nope + dv)),
            "wo": L.init_linear(ks[3], H * dv, D, dt, reps
                                ).reshape(reps + (H, dv, D)),
        }
    return {
        "wq": L.init_linear(ks[0], D, a.num_heads * a.head_dim, dt, reps
                            ).reshape(reps + (D, a.num_heads, a.head_dim)),
        "wk": L.init_linear(ks[1], D, a.num_kv_heads * a.head_dim, dt, reps
                            ).reshape(reps + (D, a.num_kv_heads, a.head_dim)),
        "wv": L.init_linear(ks[2], D, a.num_kv_heads * a.head_dim, dt, reps
                            ).reshape(reps + (D, a.num_kv_heads, a.head_dim)),
        "wo": L.init_linear(ks[3], a.num_heads * a.head_dim, D, dt, reps
                            ).reshape(reps + (a.num_heads, a.head_dim, D)),
    }


def _init_mlp(key, cfg: ModelConfig, reps: tuple[int, ...]) -> dict:
    dt = cfg.pdtype()
    ks = jax.random.split(key, 3)
    D, F = cfg.d_model, cfg.d_ff
    if cfg.activation == "gelu":
        return {
            "w_fc": L.init_linear(ks[0], D, F, dt, reps),
            "b_fc": jnp.zeros(reps + (F,), dt),
            "w_proj": L.init_linear(ks[1], F, D, dt, reps),
            "b_proj": jnp.zeros(reps + (D,), dt),
        }
    return {
        "w_gate": L.init_linear(ks[0], D, F, dt, reps),
        "w_up": L.init_linear(ks[1], D, F, dt, reps),
        "w_down": L.init_linear(ks[2], F, D, dt, reps),
    }


def _init_norm(cfg: ModelConfig, reps: tuple[int, ...]) -> dict:
    dt = cfg.pdtype()
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.zeros(reps + (cfg.d_model,), dt)}
    return {"scale": jnp.ones(reps + (cfg.d_model,), dt),
            "bias": jnp.zeros(reps + (cfg.d_model,), dt)}


def _init_layer(key, kind: str, cfg: ModelConfig,
                reps: tuple[int, ...]) -> dict:
    ks = jax.random.split(key, 4)
    p: dict[str, Any] = {"ln1": _init_norm(cfg, reps)}
    if kind in ("dense", "moe", "local_attn", "cross"):
        p["attn"] = _init_attn(ks[0], cfg, reps)
        p["ln2"] = _init_norm(cfg, reps)
        if kind == "moe":
            p["ffn"] = moe_lib.init_moe_params(ks[1], cfg.d_model, cfg.moe,
                                               cfg.pdtype(), reps)
        else:
            p["ffn"] = _init_mlp(ks[1], cfg, reps)
        if kind == "cross":
            p["xattn"] = _init_attn(ks[2], cfg, reps)
            p["ln_x"] = _init_norm(cfg, reps)
    elif kind == "ssm":
        p["ssm"] = ssm_lib.init_ssm_params(ks[0], cfg.d_model, cfg.ssm,
                                           cfg.pdtype(), reps)
    elif kind == "rglru":
        p["rglru"] = rglru_lib.init_rglru_params(ks[0], cfg.d_model,
                                                 cfg.rglru, cfg.pdtype(),
                                                 reps)
        p["ln2"] = _init_norm(cfg, reps)
        p["ffn"] = _init_mlp(ks[1], cfg, reps)
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return p


def init_params(key: jax.Array, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 8)
    dt = cfg.pdtype()
    params: dict[str, Any] = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model),
                                    jnp.float32) * 0.02).astype(dt),
        "final_norm": _init_norm(cfg, ()),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_linear(ks[1], cfg.d_model, cfg.vocab_size,
                                          dt)
    groups = []
    for gi, (unit, reps) in enumerate(block_groups(cfg)):
        gkey = jax.random.fold_in(ks[2], gi)
        unit_params = []
        for ui, kind in enumerate(unit):
            unit_params.append(_init_layer(jax.random.fold_in(gkey, ui),
                                           kind, cfg, (reps,)))
        groups.append(unit_params)
    params["groups"] = groups
    if cfg.is_encdec:
        enc = {"layers": _init_layer(ks[3], "dense",
                                     _encoder_cfg(cfg),
                                     (cfg.encoder_layers,)),
               "final_norm": _init_norm(cfg, ())}
        params["encoder"] = enc
    return params


def _encoder_cfg(cfg: ModelConfig) -> ModelConfig:
    import dataclasses
    return dataclasses.replace(
        cfg, attention=dataclasses.replace(cfg.attention, causal=False))


# ---------------------------------------------------------------------------
# Layer bodies
# ---------------------------------------------------------------------------

def _attn_apply(p: dict, x: jax.Array, cfg: ModelConfig, pos_q, pos_k,
                k_ext=None, v_ext=None, window=None, causal=None,
                q_chunk=2048):
    """Projection + attention + output projection.  Returns (out, (k, v))."""
    a = cfg.attention
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    q = constrain(q, "batch", None, "tp", None)
    if k_ext is None:
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
        k = constrain(k, "batch", None, "tp", None)
        v = constrain(v, "batch", None, "tp", None)
        q = L.rope(q, pos_q, a.rope_theta)
        k = L.rope(k, pos_k, a.rope_theta)
    else:  # cross-attention: K/V precomputed from encoder output
        k, v = k_ext, v_ext
    import dataclasses
    acfg = dataclasses.replace(
        a,
        causal=a.causal if causal is None else causal,
        window=a.window if window is None else window)
    out = L.attention(q, k, v, pos_q, pos_k, acfg, q_chunk=q_chunk)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    out = constrain(out, "batch", None, None)
    return out, (k, v)


def _latent_project(p: dict, x: jax.Array, cfg: ModelConfig, positions):
    """Latent attention's projections of x (B, S, D) at ``positions`` (B,
    S): the query's no-rope and roped parts (B, S, H, nope / rope) and
    the token's cache row (B, S, rank + rope), the normed latent beside
    the roped key part all heads share."""
    a = cfg.attention
    r, nope = a.kv_lora_rank, a.qk_nope_head_dim
    inv_freq = L.yarn_inv_freq(a, a.qk_rope_head_dim)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    q_pe = L.rope(q[..., nope:], positions, a.rope_theta, inv_freq)
    kv = x @ p["wkv_a"].astype(x.dtype)
    c = L.rms_norm(kv[..., :r], p["kv_norm"]["scale"])
    k_pe = L.rope(kv[..., None, r:], positions, a.rope_theta, inv_freq)
    return q[..., :nope], q_pe, jnp.concatenate([c, k_pe[..., 0, :]], -1)


def _latent_attn_fwd(p: dict, x: jax.Array, cfg: ModelConfig, positions,
                     q_chunk=2048):
    """Latent attention over a whole sequence.  Returns (out, ckv)."""
    with jax.named_scope("latent_attention"):
        q_nope, q_pe, ckv = _latent_project(p, x, cfg, positions)
        out = L.latent_attention(q_nope, q_pe, ckv,
                                 p["wkv_b"].astype(x.dtype), positions,
                                 positions, cfg.attention, q_chunk=q_chunk)
        out = jnp.einsum("bshv,hvd->bsd", out, p["wo"].astype(x.dtype))
    return constrain(out, "batch", None, None), ckv


def _ffn_apply(p: dict, x: jax.Array, cfg: ModelConfig, kind: str):
    """The layer's MLP or expert layer.  Returns (out, pairs): a
    held-share expert layer's group sizes (held experts,) int32, else
    None."""
    pairs = None
    if kind == "moe" and cfg.moe.held_experts:
        out, pairs = moe_lib.held_moe_block(p, x, cfg.moe)
    elif kind == "moe":
        out = moe_lib.moe_block(p, x, cfg.moe)
    elif cfg.activation == "gelu":
        h = jax.nn.gelu(x @ p["w_fc"].astype(x.dtype)
                        + p["b_fc"].astype(x.dtype), approximate=True)
        h = constrain(h, "batch", None, "tp")
        out = h @ p["w_proj"].astype(x.dtype) + p["b_proj"].astype(x.dtype)
    else:
        h = (jax.nn.silu(x @ p["w_gate"].astype(x.dtype))
             * (x @ p["w_up"].astype(x.dtype)))
        h = constrain(h, "batch", None, "tp")
        out = h @ p["w_down"].astype(x.dtype)
    return constrain(out, "batch", None, None), pairs


def _layer_fwd(kind: str, p: dict, x: jax.Array, cfg: ModelConfig,
               positions: jax.Array, enc_kv=None, q_chunk=2048):
    """Full-sequence layer forward.  Returns (x, cache_entry)."""
    norm = lambda n, h: L.apply_norm(cfg.norm, h, n)
    cache: dict[str, Any] = {}
    if kind in ("dense", "moe") and cfg.attention.latent:
        h, ckv = _latent_attn_fwd(p["attn"], norm(p["ln1"], x), cfg,
                                  positions, q_chunk=q_chunk)
        x = x + h
        x = x + _ffn_apply(p["ffn"], norm(p["ln2"], x), cfg, kind)[0]
        cache = {"ckv": ckv}
    elif kind in ("dense", "moe", "local_attn", "cross"):
        window = cfg.rglru.window if (kind == "local_attn" and cfg.rglru) \
            else cfg.attention.window
        h, (k, v) = _attn_apply(p["attn"], norm(p["ln1"], x), cfg,
                                positions, positions, window=window,
                                q_chunk=q_chunk)
        x = x + h
        if kind == "cross":
            ek, ev = enc_kv
            enc_pos = jnp.zeros(ek.shape[:2], jnp.int32)
            h, _ = _attn_apply(p["xattn"], norm(p["ln_x"], x), cfg,
                               positions, enc_pos, k_ext=ek, v_ext=ev,
                               causal=False, q_chunk=q_chunk)
            x = x + h
        x = x + _ffn_apply(p["ffn"], norm(p["ln2"], x), cfg, kind)[0]
        if kind == "local_attn" and cfg.rglru:
            W = cfg.rglru.window
            cache = {"k": k[:, -W:], "v": v[:, -W:],
                     "pos": positions[:, -W:]}
        else:
            cache = {"k": k, "v": v}
    elif kind == "ssm":
        h, cache = ssm_lib.ssm_block(p["ssm"], norm(p["ln1"], x),
                                     cfg.d_model, cfg.ssm)
        x = x + h
    elif kind == "rglru":
        h, cache = rglru_lib.rglru_block(p["rglru"], norm(p["ln1"], x),
                                         cfg.rglru)
        x = x + h
        x = x + _ffn_apply(p["ffn"], norm(p["ln2"], x), cfg, "dense")[0]
    return x, cache


def _layer_decode(kind: str, p: dict, x: jax.Array, cache: dict,
                  cfg: ModelConfig, pos: jax.Array, enc_kv=None):
    """Single-token layer step against a cache.  Returns (x, new_cache,
    pairs): a held-share expert layer's group sizes, else None."""
    norm = lambda n, h: L.apply_norm(cfg.norm, h, n)
    B = x.shape[0]
    pos_b = jnp.broadcast_to(pos, (B,))
    if kind in ("dense", "moe") and cfg.attention.latent:
        a, ap = cfg.attention, p["attn"]
        with jax.named_scope("latent_attention"):
            q_nope, q_pe, ckv = _latent_project(ap, norm(p["ln1"], x), cfg,
                                                pos_b[:, None])
            ckv_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["ckv"], _quant_kv(ckv, cfg), pos, 1)
            out = L.latent_decode_attention(
                q_nope, q_pe, _dequant_kv(ckv_cache, cfg),
                ap["wkv_b"].astype(x.dtype), a, cache_len=pos_b + 1)
            x = x + jnp.einsum("bshv,hvd->bsd", out,
                               ap["wo"].astype(x.dtype))
        h, pairs = _ffn_apply(p["ffn"], norm(p["ln2"], x), cfg, kind)
        return x + h, {"ckv": ckv_cache}, pairs
    if kind in ("dense", "moe", "local_attn", "cross"):
        a = cfg.attention
        hin = norm(p["ln1"], x)
        ap = p["attn"]
        q = jnp.einsum("bsd,dhk->bshk", hin, ap["wq"].astype(x.dtype))
        k = jnp.einsum("bsd,dhk->bshk", hin, ap["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dhk->bshk", hin, ap["wv"].astype(x.dtype))
        q = L.rope(q, pos_b[:, None], a.rope_theta)
        k = L.rope(k, pos_b[:, None], a.rope_theta)
        if kind == "local_attn" and cfg.rglru:
            W = cfg.rglru.window
            slot = pos % W
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], _quant_kv(k, cfg), slot, 1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], _quant_kv(v, cfg), slot, 1)
            pos_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["pos"], pos_b[:, None], slot, 1)
            valid = (pos_cache <= pos) & (pos_cache > pos - W)
            bias = jnp.where(valid, 0.0, -0.7 * np.finfo(np.float32).max)
            qg = q.reshape(B, 1, a.num_kv_heads, a.group_size, a.head_dim)
            out = L._attend(qg, _dequant_kv(k_cache, cfg),
                            _dequant_kv(v_cache, cfg),
                            bias[:, None, None, None, :],
                            a.attn_logit_softcap)
            new_cache = {"k": k_cache, "v": v_cache, "pos": pos_cache}
        else:
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], _quant_kv(k, cfg), pos, 1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], _quant_kv(v, cfg), pos, 1)
            out = L.decode_attention(q, _dequant_kv(k_cache, cfg),
                                     _dequant_kv(v_cache, cfg), pos_b, a,
                                     cache_len=pos_b + 1)
            out = out.reshape(B, 1, a.num_kv_heads, a.group_size, a.head_dim)
            new_cache = {"k": k_cache, "v": v_cache}
        out = out.reshape(B, 1, a.num_heads, a.head_dim)
        h = jnp.einsum("bshk,hkd->bsd", out, ap["wo"].astype(x.dtype))
        x = x + h
        if kind == "cross":
            ek, ev = enc_kv
            enc_pos = jnp.zeros(ek.shape[:2], jnp.int32)
            h, _ = _attn_apply(p["xattn"], norm(p["ln_x"], x), cfg,
                               pos_b[:, None], enc_pos, k_ext=ek, v_ext=ev,
                               causal=False)
            x = x + h
        h, pairs = _ffn_apply(p["ffn"], norm(p["ln2"], x), cfg, kind)
        return x + h, new_cache, pairs
    if kind == "ssm":
        h, new_cache = ssm_lib.ssm_decode_step(p["ssm"], norm(p["ln1"], x),
                                               cache, cfg.d_model, cfg.ssm)
        return x + h, new_cache, None
    if kind == "rglru":
        h, new_cache = rglru_lib.rglru_decode_step(p["rglru"],
                                                   norm(p["ln1"], x), cache,
                                                   cfg.rglru)
        x = x + h
        x = x + _ffn_apply(p["ffn"], norm(p["ln2"], x), cfg, "dense")[0]
        return x, new_cache, None
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Remat policy
# ---------------------------------------------------------------------------

def _remat(fn, cfg: ModelConfig):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "minimal":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:  # "full"
        policy = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=policy)


# ---------------------------------------------------------------------------
# Cache init
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> list:
    """Stacked cache pytrees aligned with params['groups']."""
    a = cfg.attention
    dt = cfg.cdtype()
    kv_dt = _cache_dtype(cfg)
    groups = []
    for (unit, reps) in block_groups(cfg):
        unit_caches = []
        for kind in unit:
            if kind == "ssm":
                c = ssm_lib.init_ssm_cache(batch, cfg.d_model, cfg.ssm, dt)
            elif kind == "rglru":
                c = rglru_lib.init_rglru_cache(batch, cfg.d_model, cfg.rglru,
                                               dt)
            elif a.latent:
                c = {"ckv": jnp.zeros((batch, max_len, a.kv_lora_rank
                                       + a.qk_rope_head_dim), kv_dt)}
            elif kind == "local_attn":
                W = cfg.rglru.window if cfg.rglru else a.window
                c = {"k": jnp.zeros((batch, W, a.num_kv_heads, a.head_dim),
                                    kv_dt),
                     "v": jnp.zeros((batch, W, a.num_kv_heads, a.head_dim),
                                    kv_dt),
                     "pos": -jnp.ones((batch, W), jnp.int32)}
            else:
                c = {"k": jnp.zeros((batch, max_len, a.num_kv_heads,
                                     a.head_dim), kv_dt),
                     "v": jnp.zeros((batch, max_len, a.num_kv_heads,
                                     a.head_dim), kv_dt)}
            unit_caches.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x, (reps,) + x.shape), c))
        groups.append(unit_caches)
    return groups


# ---------------------------------------------------------------------------
# Full passes
# ---------------------------------------------------------------------------

def _embed_inputs(params, tokens, cfg: ModelConfig, extra_embeds=None):
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.cdtype())
    if cfg.family == "hybrid":  # gemma-style embedding scale
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    if cfg.num_image_tokens and extra_embeds is not None:
        n = cfg.num_image_tokens
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x[:, n:]], axis=1)
    return constrain(x, "batch", None, None)


def _encoder_fwd(params, audio_embeds, cfg: ModelConfig):
    """Whisper-style encoder over stub frame embeddings (B, S_enc, D)."""
    x = audio_embeds.astype(cfg.cdtype())
    pos = jnp.broadcast_to(jnp.arange(x.shape[1], dtype=jnp.int32),
                           x.shape[:2])
    ecfg = _encoder_cfg(cfg)
    p = params["encoder"]["layers"]

    def body(h, pl):
        h, _ = _layer_fwd("dense", pl, h, ecfg, pos)
        return h, None

    x, _ = jax.lax.scan(_remat(body, cfg), x, p)
    return L.apply_norm(cfg.norm, x, params["encoder"]["final_norm"])


def _enc_cross_kv(params, enc_out, cfg: ModelConfig):
    """Precompute per-decoder-layer cross-attention K/V from encoder out."""
    kvs = []
    for unit_params in params["groups"]:
        for p in unit_params:
            xp = p["xattn"]
            k = jnp.einsum("bsd,rdhk->rbshk", enc_out,
                           xp["wk"].astype(enc_out.dtype))
            v = jnp.einsum("bsd,rdhk->rbshk", enc_out,
                           xp["wv"].astype(enc_out.dtype))
            kvs.append((k, v))
    return kvs


def _lm_logits(params, x, cfg: ModelConfig):
    x = L.apply_norm(cfg.norm, x, params["final_norm"])
    if cfg.tie_embeddings:
        w = params["embed"].astype(x.dtype).T
    else:
        w = params["lm_head"].astype(x.dtype)
    return constrain(x @ w, "batch", None, "tp")


def forward(params: dict, tokens: jax.Array, cfg: ModelConfig, *,
            extra_embeds: Optional[jax.Array] = None,
            audio_embeds: Optional[jax.Array] = None,
            q_chunk: int = 2048, want_cache: bool = False):
    """Full-sequence forward.  Returns (logits, cache-or-None)."""
    B, S = tokens.shape
    x = _embed_inputs(params, tokens, cfg, extra_embeds)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    enc_kvs = None
    if cfg.is_encdec:
        enc_out = _encoder_fwd(params, audio_embeds, cfg)
        enc_kvs = _enc_cross_kv(params, enc_out, cfg)

    caches = []
    gi_cross = 0
    for g, (unit, reps) in enumerate(block_groups(cfg)):
        unit_params = params["groups"][g]

        if cfg.is_encdec:
            ek, ev = enc_kvs[gi_cross]
            gi_cross += 1

            def body(h, xs):
                pl, ekl, evl = xs
                h, c = _layer_fwd("cross", pl, h, cfg, positions,
                                  enc_kv=(ekl, evl), q_chunk=q_chunk)
                return h, c

            x, cache = jax.lax.scan(_remat(body, cfg), x,
                                    (unit_params[0], ek, ev))
            caches.append([cache])
            continue

        def body(h, pl):
            cs = []
            for kind, pk in zip(unit, pl):
                h, c = _layer_fwd(kind, pk, h, cfg, positions,
                                  q_chunk=q_chunk)
                cs.append(c)
            return h, cs

        x, cache = jax.lax.scan(_remat(body, cfg), x, unit_params)
        caches.append(cache)

    logits = _lm_logits(params, x, cfg)
    return logits, (caches if want_cache else None)


def forward_train(params, tokens, targets, cfg: ModelConfig, *,
                  loss_mask=None, extra_embeds=None, audio_embeds=None,
                  q_chunk: int = 2048):
    """Token-mean cross-entropy loss (fp32 logsumexp)."""
    logits, _ = forward(params, tokens, cfg, extra_embeds=extra_embeds,
                        audio_embeds=audio_embeds, q_chunk=q_chunk)
    from repro.models.loss import cross_entropy
    if loss_mask is None and cfg.num_image_tokens:
        B, S = tokens.shape
        pos = jnp.arange(S)[None, :]
        loss_mask = jnp.broadcast_to(pos >= cfg.num_image_tokens, (B, S))
    return cross_entropy(logits, targets, loss_mask)


def prefill(params, tokens, cfg: ModelConfig, max_len: int, *,
            extra_embeds=None, audio_embeds=None, q_chunk: int = 2048):
    """Run the prompt; returns (last-position logits, caches @ max_len)."""
    logits, caches = forward(params, tokens, cfg, extra_embeds=extra_embeds,
                             audio_embeds=audio_embeds, q_chunk=q_chunk,
                             want_cache=True)
    S = tokens.shape[1]
    padded = []
    for g, (unit, reps) in enumerate(block_groups(cfg)):
        unit_caches = []
        for u, kind in enumerate(unit):
            c = caches[g][u]
            if "ckv" in c:
                pad = [(0, 0)] * c["ckv"].ndim
                pad[2] = (0, max_len - S)
                c = {"ckv": jnp.pad(_quant_kv(c["ckv"], cfg), pad)}
            elif kind in ("dense", "moe", "cross") and "k" in c:
                pad = [(0, 0)] * c["k"].ndim
                pad[2] = (0, max_len - S)
                c = {"k": jnp.pad(_quant_kv(c["k"], cfg), pad),
                     "v": jnp.pad(_quant_kv(c["v"], cfg), pad)}
            elif kind == "local_attn" and "k" in c:
                c = dict(c, k=_quant_kv(c["k"], cfg),
                         v=_quant_kv(c["v"], cfg))
            unit_caches.append(c)
        padded.append(unit_caches)
    if cfg.is_encdec:
        enc_out = _encoder_fwd(params, audio_embeds, cfg)
        return logits[:, -1, :], (padded, _enc_cross_kv(params, enc_out, cfg))
    return logits[:, -1, :], padded


def decode_layers(params, token: jax.Array, caches, pos: jax.Array,
                  cfg: ModelConfig, *, enc_kvs=None):
    """The layers of one serving step: token (B, 1) at position ``pos``
    (scalar int32).

    Returns (x (B, 1, D) before the final norm, new caches, the held-share
    expert layers' group sizes (MoE layers, held experts) int32 or
    ``None`` without such layers).  Encoder-decoder caches come and go as
    ``(caches, enc_kvs)`` unless ``enc_kvs`` is given.
    """
    x = _embed_inputs(params, token, cfg)
    if cfg.is_encdec and enc_kvs is None:
        caches, enc_kvs = caches

    new_caches, pairs = [], []
    gi = 0
    for g, (unit, reps) in enumerate(block_groups(cfg)):
        unit_params = params["groups"][g]
        unit_cache = caches[g]

        if cfg.is_encdec:
            ek, ev = enc_kvs[gi]
            gi += 1

            def body(h, xs):
                pl, cl, ekl, evl = xs
                h, c, _ = _layer_decode("cross", pl, h, cl, cfg, pos,
                                        enc_kv=(ekl, evl))
                return h, c

            x, ncache = jax.lax.scan(body, x,
                                     (unit_params[0], unit_cache[0], ek, ev))
            new_caches.append([ncache])
            continue

        def body(h, xs):
            pl, cl = xs
            ncs, prs = [], []
            for kind, pk, ck in zip(unit, pl, cl):
                h, nc, pr = _layer_decode(kind, pk, h, ck, cfg, pos)
                ncs.append(nc)
                prs.append(pr)
            return h, (ncs, prs)

        x, (ncache, prs) = jax.lax.scan(body, x, (unit_params, unit_cache))
        new_caches.append(ncache)
        pairs.extend(pr for pr in prs if pr is not None)

    if cfg.is_encdec:
        new_caches = (new_caches, enc_kvs)
    return x, new_caches, (jnp.concatenate(pairs) if pairs else None)


def decode_step(params, token: jax.Array, caches, pos: jax.Array,
                cfg: ModelConfig, *, enc_kvs=None):
    """One serving step: token (B, 1) at position ``pos`` (scalar int32).

    Returns (logits (B, V), new caches).  No caller donates the caches:
    ``launch/steps.py``'s jitted serve step and ``ProgressiveServer``'s
    ``hidden_step`` each write the updated caches to new buffers.
    """
    x, new_caches, _ = decode_layers(params, token, caches, pos, cfg,
                                     enc_kvs=enc_kvs)
    logits = _lm_logits(params, x, cfg)
    return logits[:, 0, :], new_caches


# ---------------------------------------------------------------------------
# Parameter accounting (roofline MODEL_FLOPS)
# ---------------------------------------------------------------------------

def count_params(params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))


def active_params(cfg: ModelConfig, total: int) -> int:
    """Active parameters per token (MoE: only top-k experts count; of a
    held share, the share's expected top_k * held / num_experts)."""
    if cfg.family != "moe":
        return total
    m = cfg.moe
    per_expert = 3 * cfg.d_model * m.d_ff_expert
    n_moe_layers = sum(reps for unit, reps in block_groups(cfg)
                       for kind in unit if kind == "moe")
    held = m.held_experts[1] if m.held_experts else m.num_experts
    inactive = (per_expert * n_moe_layers * held * (m.num_experts - m.top_k)
                // m.num_experts)
    return total - inactive
