"""Shared neural-net layers: norms, RoPE, GQA and latent attention, MLPs,
embeddings.

All layers are pure functions over explicit parameter pytrees (no framework).
Attention is grouped-query throughout: queries are reshaped to
``(B, S, n_kv, group, head_dim)`` so K/V are never repeated — the grouped
einsum keeps the KV cache memory footprint exact, which matters for the
decode_32k/long_500k roofline cells.

Prefill attention over long sequences is query-chunked (lax.scan over query
blocks with an online max/sum) so the ``S_q x S_kv`` score matrix is never
materialised — the jnp twin of the Pallas flash-attention kernel in
``repro.kernels.flash_attention``.

Latent attention (DeepSeek-V2's MLA) caches one ``kv_lora_rank +
qk_rope_head_dim`` latent per token, shared by every head.  Prefill
expands it into per-head keys and values (:func:`latent_attention`);
decode folds the up-projection into the query and the output instead
and attends over the latent cache itself (:func:`latent_decode_attention`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AttentionConfig

__all__ = [
    "rms_norm", "layer_norm", "rope", "yarn_inv_freq", "yarn_mscale",
    "attention", "decode_attention", "latent_attention",
    "latent_decode_attention", "mlp_swiglu", "mlp_gelu", "init_linear",
    "init_norm",
]

_NEG_INF = -0.7 * float(np.finfo(np.float32).max)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dtype)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dtype)


def apply_norm(norm_kind: str, x: jax.Array, params: dict) -> jax.Array:
    if norm_kind == "rmsnorm":
        return rms_norm(x, params["scale"])
    return layer_norm(x, params["scale"], params["bias"])


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float,
         inv_freq: Optional[np.ndarray] = None) -> jax.Array:
    """Apply RoPE to ``x (..., S, n, head_dim)`` given ``positions (..., S)``.

    ``inv_freq`` (head_dim/2,) replaces ``theta``'s frequencies (YaRN)."""
    head_dim = x.shape[-1]
    if inv_freq is None:
        fraction = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
        timescale = theta**fraction                   # (head_dim/2,)
        angles = (positions[..., None].astype(jnp.float32)
                  / timescale[None, :])               # (..., S, head_dim/2)
    else:
        angles = (positions[..., None].astype(jnp.float32)
                  * jnp.asarray(inv_freq, jnp.float32))
    angles = angles[..., None, :]                     # broadcast over heads
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


#: YaRN's ramp ends: dims turning at least this often within the original
#: context keep their frequency, those turning at most this often are
#: scaled (arXiv:2309.00071 and DeepSeek-V2's setting)
YARN_BETA_FAST, YARN_BETA_SLOW = 32.0, 1.0


def yarn_inv_freq(cfg: AttentionConfig, dim: int) -> np.ndarray:
    """Rotary frequencies of ``dim`` rotated dims under ``cfg``'s YaRN
    scaling (arXiv:2309.00071): dims that turn fast enough to see a whole
    period within the original context keep their frequency, slow ones
    are divided by ``rope_factor``, with a linear ramp between."""
    base = cfg.rope_theta
    f = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.rope_factor == 1.0:
        return f.astype(np.float32)

    def d(rotations):
        return (dim * np.log(cfg.rope_original_max_pos
                             / (2 * np.pi * rotations))
                / (2 * np.log(base)))

    lo = max(int(np.floor(d(YARN_BETA_FAST))), 0)
    hi = min(int(np.ceil(d(YARN_BETA_SLOW))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    keep = 1.0 - ramp
    return (f / cfg.rope_factor * (1 - keep) + f * keep).astype(np.float32)


def yarn_mscale(cfg: AttentionConfig) -> float:
    """The factor YaRN scales the attention scores by, twice over (as
    DeepSeek-V2 applies ``mscale_all_dim``); 1 without scaling."""
    if cfg.rope_factor <= 1.0 or not cfg.rope_mscale_all_dim:
        return 1.0
    m = 0.1 * cfg.rope_mscale_all_dim * np.log(cfg.rope_factor) + 1.0
    return float(m * m)


# ---------------------------------------------------------------------------
# Attention (grouped-query; full / causal / sliding-window; chunked prefill)
# ---------------------------------------------------------------------------

def _mask_bias(pos_q: jax.Array, pos_k: jax.Array, causal: bool,
               window: Optional[int], kv_valid: Optional[jax.Array] = None):
    """(B, 1, 1, Sq, Skv) additive mask bias from position comparisons."""
    ok = jnp.ones(pos_q.shape[-1:] + pos_k.shape[-1:], dtype=bool)
    dq, dk = pos_q[..., :, None], pos_k[..., None, :]
    if causal:
        ok = ok & (dk <= dq)
    if window is not None:
        ok = ok & (dk > dq - window)
    if kv_valid is not None:
        ok = ok & kv_valid[..., None, :]
    # (B, Sq, Skv) -> (B, 1, 1, Sq, Skv): broadcasts over (n_kv, G)
    return jnp.where(ok, 0.0, _NEG_INF)[..., None, None, :, :]


def _attend(q: jax.Array, k: jax.Array, v: jax.Array, bias: jax.Array,
            softcap: Optional[float],
            scale: Optional[float] = None) -> jax.Array:
    """Grouped attention core.

    q: (B, Sq, n_kv, G, Dh); k: (B, Skv, n_kv, Dh); v: (B, Skv, n_kv, Dv);
    bias: broadcastable to (B, n_kv, G, Sq, Skv).  Returns (B, Sq, n_kv, G,
    Dv).  ``scale`` defaults to ``Dh ** -0.5``.
    """
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        logits = jnp.tanh(logits / softcap) * softcap
    logits = logits + bias
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              pos_q: jax.Array, pos_k: jax.Array, cfg: AttentionConfig,
              *, q_chunk: int = 2048,
              kv_valid: Optional[jax.Array] = None,
              scale: Optional[float] = None) -> jax.Array:
    """Full attention for train/prefill.

    q: (B, Sq, n_heads, Dh); k: (B, Skv, n_kv, Dh); v: (B, Skv, n_kv, Dv);
    positions are (B, S).  Query-chunked when Sq > q_chunk so scores never
    materialise at S^2.  Returns (B, Sq, n_heads, Dv).
    """
    B, Sq, H, Dh = q.shape
    Dv = v.shape[-1]
    n_kv, G = cfg.num_kv_heads, cfg.group_size
    qg = q.reshape(B, Sq, n_kv, G, Dh)

    def block(q_blk, pos_blk):
        bias = _mask_bias(pos_blk, pos_k, cfg.causal, cfg.window, kv_valid)
        return _attend(q_blk, k, v, bias, cfg.attn_logit_softcap, scale)

    if Sq <= q_chunk:
        out = block(qg, pos_q)
    else:
        assert Sq % q_chunk == 0, (Sq, q_chunk)
        nblk = Sq // q_chunk
        qs = qg.reshape(B, nblk, q_chunk, n_kv, G, Dh).swapaxes(0, 1)
        ps = pos_q.reshape(B, nblk, q_chunk).swapaxes(0, 1)
        out = jax.lax.map(lambda args: block(*args), (qs, ps))
        out = out.swapaxes(0, 1).reshape(B, Sq, n_kv, G, Dv)
    return out.reshape(B, Sq, H, Dv)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array, cfg: AttentionConfig,
                     cache_len: jax.Array) -> jax.Array:
    """Single-token attention against a (B, S_cache, n_kv, Dh) KV cache.

    q: (B, 1, n_heads, Dh); ``pos`` (B,) is the new token's position;
    ``cache_len`` (B,) marks how many cache slots are valid.
    """
    B, _, H, Dh = q.shape
    n_kv, G = cfg.num_kv_heads, cfg.group_size
    S = k_cache.shape[1]
    qg = q.reshape(B, 1, n_kv, G, Dh)
    slots = jnp.arange(S, dtype=jnp.int32)[None, :]           # (1, S)
    valid = slots < cache_len[:, None]
    if cfg.window is not None:
        valid = valid & (slots > (pos[:, None] - cfg.window))
    bias = jnp.where(valid, 0.0, _NEG_INF)[:, None, None, None, :]
    out = _attend(qg, k_cache, v_cache, bias, cfg.attn_logit_softcap)
    return out.reshape(B, 1, H, Dh)


def latent_attention(q_nope: jax.Array, q_pe: jax.Array, ckv: jax.Array,
                     w_kv_b: jax.Array, pos_q: jax.Array, pos_k: jax.Array,
                     cfg: AttentionConfig, *,
                     q_chunk: int = 2048) -> jax.Array:
    """Latent attention over a whole sequence, the latent expanded.

    q_nope (B, Sq, H, nope), q_pe (B, Sq, H, rope) roped; ckv (B, Skv,
    rank + rope): the normed latent and the roped key part all heads
    share; w_kv_b (rank, H, nope + v_head_dim).  Returns (B, Sq, H,
    v_head_dim).
    """
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    kv = jnp.einsum("btr,rhk->bthk", ckv[..., :r], w_kv_b)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_pe = jnp.broadcast_to(ckv[:, :, None, r:],
                            k_nope.shape[:3] + (cfg.qk_rope_head_dim,))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, k_pe], axis=-1)
    return attention(q, k, v, pos_q, pos_k, cfg, q_chunk=q_chunk,
                     scale=yarn_mscale(cfg) / np.sqrt(q.shape[-1]))


def latent_decode_attention(q_nope: jax.Array, q_pe: jax.Array,
                            ckv_cache: jax.Array, w_kv_b: jax.Array,
                            cfg: AttentionConfig,
                            cache_len: jax.Array) -> jax.Array:
    """One token's latent attention with the up-projection absorbed.

    The query's no-rope part goes through the key half of ``w_kv_b`` into
    the latent space, where it scores every cached latent beside the
    rope part; the weighted sum of latents goes through the value half.
    Algebraically :func:`latent_attention`; the cache is never expanded.
    q_nope (B, 1, H, nope), q_pe (B, 1, H, rope), ckv_cache (B, S, rank +
    rope); ``cache_len`` (B,) valid slots.  Returns (B, 1, H, v_head_dim).
    """
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    S = ckv_cache.shape[1]
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_kv_b[..., :nope])
    q = jnp.concatenate([q_lat, q_pe], axis=-1)            # (B,1,H,r+rope)
    scale = yarn_mscale(cfg) / np.sqrt(nope + cfg.qk_rope_head_dim)
    logits = jnp.einsum("bshc,btc->bhst", q, ckv_cache,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(S, dtype=jnp.int32)[None, :] < cache_len[:, None]
    logits = logits + jnp.where(valid, 0.0, _NEG_INF)[:, None, None, :]
    probs = jax.nn.softmax(logits, axis=-1).astype(ckv_cache.dtype)
    o_lat = jnp.einsum("bhst,btr->bshr", probs, ckv_cache[..., :r])
    return jnp.einsum("bshr,rhv->bshv", o_lat, w_kv_b[..., nope:])


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
               w_down: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def mlp_gelu(x: jax.Array, w_fc: jax.Array, b_fc: jax.Array,
             w_proj: jax.Array, b_proj: jax.Array) -> jax.Array:
    h = jax.nn.gelu(x @ w_fc + b_fc, approximate=True)
    return h @ w_proj + b_proj


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def init_linear(key: jax.Array, d_in: int, d_out: int, dtype,
                extra_dims: tuple[int, ...] = ()) -> jax.Array:
    shape = extra_dims + (d_in, d_out)
    scale = 1.0 / np.sqrt(d_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_norm(d: int, dtype, kind: str = "rmsnorm") -> dict:
    if kind == "rmsnorm":
        return {"scale": jnp.zeros((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
