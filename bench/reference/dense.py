"""Plain float32 forward pass of a dense decoder, as its papers write it.

Covers both dense blocks the benchmark serves: RMSNorm with a SwiGLU MLP
and an untied head (Llama, Yi), and LayerNorm with a tanh-GELU MLP with
biases and the head tied to the embedding (StarCoder2); grouped-query
attention and rotary position embeddings (rotate-half form) in both.
Departures from the published models, shared with the served program,
are listed in each configuration file (StarCoder2's attention biases).

It imports nothing of the program: the weights come from the seed
through ``bench.weights``, one layer at a time, and every matrix product
runs at ``Precision.HIGHEST``.  The whole sequence goes through each
layer in turn, attention in blocks of queries, so that a reference of
eight 4608-wide layers over 2080 tokens fits one chip.

``mode="fp8"`` rounds both operands of every matrix product to float8
(e4m3, one scale per tensor) first: the lower precision that serves as
the check's control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import weights as W
from bench.dims import Dims

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


def _norm(x, p, d: Dims):
    if d.norm == "rmsnorm":
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + d.eps)
        return x * (1.0 + p["scale"])
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + d.eps) * p["scale"] + p["bias"]


def _rope(x, theta):
    """x (R, S, heads, hd) at positions 0 .. S-1."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, d: Dims, mode):
    """Causal grouped-query attention, a block of queries at a time."""
    R, S = q.shape[:2]
    g = d.n_heads // d.n_kv
    q = q.reshape(R, S, d.n_kv, g, d.head_dim) / jnp.sqrt(
        jnp.float32(d.head_dim))
    outs = []
    for s0 in range(0, S, Q_BLOCK):
        qb = q[:, s0:s0 + Q_BLOCK]
        n = qb.shape[1]
        s = _ein("rqkgd,rtkd->rkgqt", qb, k[:, :s0 + n], mode)
        causal = (jnp.arange(s0 + n)[None, :]
                  <= (s0 + jnp.arange(n))[:, None])
        s = jnp.where(causal, s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        outs.append(_ein("rkgqt,rtkd->rqkgd", pr, v[:, :s0 + n], mode))
    return jnp.concatenate(outs, 1).reshape(R, S, d.n_heads, d.head_dim)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer(key_data, i, x, d: Dims, mode):
    p = W.layer_params(key_data, d, i)
    a = p["attn"]
    h = _norm(x, p["ln1"], d)
    q = _rope(_ein("rsd,dhk->rshk", h, a["wq"], mode), d.rope_theta)
    k = _rope(_ein("rsd,dhk->rshk", h, a["wk"], mode), d.rope_theta)
    v = _ein("rsd,dhk->rshk", h, a["wv"], mode)
    x = x + _ein("rshk,hkd->rsd", _attention(q, k, v, d, mode), a["wo"],
                 mode)
    h = _norm(x, p["ln2"], d)
    f = p["ffn"]
    if d.act == "silu":
        u = (jax.nn.silu(_ein("rsd,df->rsf", h, f["w_gate"], mode))
             * _ein("rsd,df->rsf", h, f["w_up"], mode))
        return x + _ein("rsf,fd->rsd", u, f["w_down"], mode)
    u = _ein("rsd,df->rsf", h, f["w_fc"], mode) + f.get("b_fc", 0.0)
    c = jnp.sqrt(2.0 / jnp.pi)
    u = 0.5 * u * (1.0 + jnp.tanh(c * (u + 0.044715 * u ** 3)))
    return x + _ein("rsf,fd->rsd", u, f["w_proj"], mode) + f.get(
        "b_proj", 0.0)


@functools.partial(jax.jit, static_argnums=2)
def _embed(key_data, tokens, d: Dims):
    return jnp.take(W.embed(key_data, d), tokens, axis=0)


def hidden(key_data, tokens, d: Dims, mode: str = "f32"):
    """Final-normed hidden states (R, S, d_model) of token rows (R, S)."""
    if mode not in MODES:
        raise ValueError(f"mode {mode!r} not one of {MODES}")
    x = _embed(key_data, jnp.asarray(tokens, jnp.int32), d)
    for i in range(d.n_layers):
        x = _layer(key_data, jnp.int32(i), x, d, mode)
    return x


@functools.partial(jax.jit, static_argnums=(2, 3))
def logits(key_data, h, d: Dims, mode: str = "f32"):
    """LM-head logits of hidden states ``h`` (..., d_model)."""
    return _ein("...d,dv->...v", _norm(h, W.final_norm(key_data, d), d),
                W.head(key_data, d), mode)
