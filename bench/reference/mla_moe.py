"""Plain float32 forward pass of a latent-attention mixture-of-experts
decoder (DeepSeek-V2, arXiv:2405.04434), as its paper writes it.

Latent attention is computed expanded: the normed latent goes through the
up-projection into every head's keys and values, the roped key part is
shared by the heads, and scores are ``(q_nope . k_nope + q_pe . k_pe)``
times ``(qk_nope + qk_rope)^-1/2 * mscale^2`` (YaRN, whose frequencies
are computed here from the configuration's ``rope_scaling``).  An expert
layer routes in float32 over all ``n_experts`` with softmax and top-k,
and computes every held expert on every token, weighted by its gate
where it is among the token's picks and 0 elsewhere; the shared experts
are added once.  Departures shared with the served program are listed in
the configuration file.

It imports nothing of the program: the weights come from the seed
through the family's ``layer_params``, one layer at a time, and every
matrix product runs at ``Precision.HIGHEST``.  The whole sequence goes
through each layer in turn, attention in blocks of queries.

``mode="fp8"`` rounds both operands of every matrix product to float8
(e4m3, one scale per tensor) first: the lower precision that serves as
the check's control.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.families import mla_moe as F

__all__ = ["hidden", "logits", "MODES"]

MODES = ("f32", "fp8")
HI = jax.lax.Precision.HIGHEST
#: queries per attention block
Q_BLOCK = 256
_F8_MAX = 448.0


def _round(x, mode):
    if mode == "f32":
        return x
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ein(spec, a, b, mode):
    return jnp.einsum(spec, _round(a, mode), _round(b, mode), precision=HI)


def _rms(x, g, d: F.Dims):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + d.eps)
    return x * (1.0 + g["scale"])


def _inv_freq(d: F.Dims) -> np.ndarray:
    """YaRN: dims turning at least ``beta_fast`` times within the original
    context keep their frequency, those turning at most ``beta_slow``
    times are divided by the factor, with a linear ramp between."""
    dim, base = d.qk_rope, d.rope_theta
    freq = base ** (-np.arange(0, dim, 2) / dim)

    def dim_of(rotations):
        return (dim * math.log(d.rope_orig / (2 * math.pi * rotations))
                / (2 * math.log(base)))

    lo = max(math.floor(dim_of(d.beta_fast)), 0)
    hi = min(math.ceil(dim_of(d.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    return (freq / d.rope_factor * ramp + freq * (1 - ramp)).astype(
        np.float32)


def _scale(d: F.Dims) -> float:
    m = 0.1 * d.mscale_all_dim * math.log(d.rope_factor) + 1.0
    return m * m / math.sqrt(d.qk_nope + d.qk_rope)


def _rope(x, d: F.Dims):
    """x (R, S, heads, rope) at positions 0 .. S-1, rotate-half form."""
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * _inv_freq(d)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(h, a, d: F.Dims, mode):
    """Causal latent attention, expanded, a block of queries at a time."""
    r, nope = d.kv_rank, d.qk_nope
    q = _ein("rsd,dhk->rshk", h, a["wq"], mode)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], d)
    kv = _ein("rsd,dk->rsk", h, a["wkv_a"], mode)
    c = _rms(kv[..., :r], a["kv_norm"], d)
    k_pe = _rope(kv[:, :, None, r:], d)[:, :, 0]
    kvb = _ein("rsc,chk->rshk", c, a["wkv_b"], mode)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    S, outs = h.shape[1], []
    for s0 in range(0, S, Q_BLOCK):
        n = min(Q_BLOCK, S - s0)
        s = (_ein("rqhk,rthk->rhqt", q_nope[:, s0:s0 + n],
                  k_nope[:, :s0 + n], mode)
             + _ein("rqhk,rtk->rhqt", q_pe[:, s0:s0 + n], k_pe[:, :s0 + n],
                    mode)) * _scale(d)
        causal = (jnp.arange(s0 + n)[None, :]
                  <= (s0 + jnp.arange(n))[:, None])
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        outs.append(_ein("rhqt,rthv->rqhv", pr, v[:, :s0 + n], mode))
    return _ein("rshv,hvd->rsd", jnp.concatenate(outs, 1), a["wo"], mode)


def _swiglu(h, gate, up, down, mode):
    u = (jax.nn.silu(_ein("rsd,df->rsf", h, gate, mode))
         * _ein("rsd,df->rsf", h, up, mode))
    return _ein("rsf,fd->rsd", u, down, mode)


def _experts(h, f, d: F.Dims, mode):
    """The held experts' part of the layer plus the shared experts."""
    g = jax.nn.softmax(_ein("rsd,de->rse", h, f["router"], mode), axis=-1)
    top, idx = jax.lax.top_k(g, d.top_k)
    y = 0.0
    for e in range(d.n_held):
        gate = jnp.sum(jnp.where(idx == e, top, 0.0), -1)[..., None]
        y = y + gate * _swiglu(h, f["we_gate"][e], f["we_up"][e],
                               f["we_down"][e], mode)
    s = f["shared"]
    return y + _swiglu(h, s["w_gate"], s["w_up"], s["w_down"], mode)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _layer(key_data, i, x, d: F.Dims, mode, moe):
    p = F.layer_params(key_data, d, i, moe)
    x = x + _attention(_rms(x, p["ln1"], d), p["attn"], d, mode)
    h = _rms(x, p["ln2"], d)
    f = p["ffn"]
    if moe:
        return x + _experts(h, f, d, mode)
    return x + _swiglu(h, f["w_gate"], f["w_up"], f["w_down"], mode)


@functools.partial(jax.jit, static_argnums=2)
def _embed(key_data, tokens, d: F.Dims):
    return jnp.take(F.embed(key_data, d), tokens, axis=0)


def hidden(key_data, tokens, d: F.Dims, mode: str = "f32"):
    """Hidden states (R, S, d_model) of token rows (R, S), before the
    final norm (which ``logits`` applies)."""
    if mode not in MODES:
        raise ValueError(f"mode {mode!r} not one of {MODES}")
    x = _embed(key_data, jnp.asarray(tokens, jnp.int32), d)
    for i in range(d.n_layers):
        x = _layer(key_data, jnp.int32(i), x, d, mode, i >= d.n_dense)
    return x


@functools.partial(jax.jit, static_argnums=(2, 3))
def logits(key_data, h, d: F.Dims, mode: str = "f32"):
    """LM-head logits of hidden states ``h`` (..., d_model)."""
    return _ein("...d,dv->...v", _rms(h, F.final_norm(key_data, d), d),
                F.head(key_data, d), mode)
