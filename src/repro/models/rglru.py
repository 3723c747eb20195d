"""RG-LRU recurrent block (RecurrentGemma / Griffin, arXiv:2402.19427).

The temporal-mixing block is:

    branch 1: Linear(D -> D_rnn) -> GeLU
    branch 2: Linear(D -> D_rnn) -> causal depthwise Conv1D(4) -> RG-LRU
    merge:    elementwise product -> Linear(D_rnn -> D)

with the RG-LRU recurrence (all elementwise, diagonal):

    r_t = sigmoid(x_t W_a + b_a)            (recurrence gate)
    i_t = sigmoid(x_t W_x + b_x)            (input gate)
    log a_t = -c * softplus(Lambda) * r_t   (c = 8)
    h_t = a_t h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)

Train/prefill evaluates the diagonal linear recurrence with
``jax.lax.associative_scan`` (log-depth, TPU-friendly — the GPU kernel's
sequential fused scan does not transfer).  Decode carries
``h`` directly: O(D_rnn) per token, so recurrentgemma runs ``long_500k``
(its attention layers are sliding-window, cache bounded by the window).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import RGLRUConfig
from repro.launch.axes import constrain
from repro.models.layers import init_linear

__all__ = ["init_rglru_params", "rglru_block", "rglru_decode_step",
           "init_rglru_cache"]

_C = 8.0  # RG-LRU temperature


def init_rglru_params(key: jax.Array, d_model: int, cfg: RGLRUConfig, dtype,
                      extra_dims: tuple[int, ...] = ()) -> dict:
    d_rnn = cfg.d_rnn or d_model
    ks = jax.random.split(key, 6)
    shp = lambda *s: extra_dims + s
    # Lambda init so that a^c in [0.9, 0.999] (Griffin appendix)
    u = jax.random.uniform(ks[0], shp(d_rnn), jnp.float32, 0.9, 0.999)
    lam = jnp.log(jnp.expm1(-jnp.log(u) / _C))  # softplus^-1(-log(u)/c)
    return {
        "in_gelu": init_linear(ks[1], d_model, d_rnn, dtype, extra_dims),
        "in_rnn": init_linear(ks[2], d_model, d_rnn, dtype, extra_dims),
        "conv_w": (jax.random.normal(ks[3], shp(cfg.d_conv, d_rnn),
                                     jnp.float32)
                   / np.sqrt(cfg.d_conv)).astype(dtype),
        "conv_b": jnp.zeros(shp(d_rnn), dtype),
        "w_a": init_linear(ks[4], d_rnn, d_rnn, dtype, extra_dims),
        "b_a": jnp.zeros(shp(d_rnn), jnp.float32),
        "w_x": init_linear(ks[5], d_rnn, d_rnn, dtype, extra_dims),
        "b_x": jnp.zeros(shp(d_rnn), jnp.float32),
        "Lambda": lam,
        "out": init_linear(jax.random.fold_in(key, 7), d_rnn, d_model, dtype,
                           extra_dims),
    }


def init_rglru_cache(batch: int, d_model: int, cfg: RGLRUConfig,
                     dtype) -> dict:
    d_rnn = cfg.d_rnn or d_model
    return {
        "conv": jnp.zeros((batch, cfg.d_conv - 1, d_rnn), dtype),
        "h": jnp.zeros((batch, d_rnn), jnp.float32),
    }


def _rglru_gates(params: dict, x: jax.Array):
    """Common gate math. x: (..., d_rnn) -> (a, gated_input) float32."""
    xf = x.astype(jnp.float32)
    r = jax.nn.sigmoid(xf @ params["w_a"].astype(jnp.float32)
                       + params["b_a"])
    i = jax.nn.sigmoid(xf @ params["w_x"].astype(jnp.float32)
                       + params["b_x"])
    log_a = -_C * jax.nn.softplus(params["Lambda"]) * r
    a = jnp.exp(log_a)
    # sqrt(1 - a^2) computed stably via expm1: 1 - exp(2 log a)
    beta = jnp.sqrt(-jnp.expm1(2.0 * log_a))
    return a, beta * (i * xf)


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    K = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(pad[:, i: i + x.shape[1], :] * w[i][None, None, :]
              for i in range(K))
    return out + b[None, None, :]


def rglru_block(params: dict, x: jax.Array, cfg: RGLRUConfig,
                init_h=None):
    """(B, S, D) -> (y, cache).  Linear scan via associative_scan."""
    gelu_branch = jax.nn.gelu(
        constrain(x @ params["in_gelu"].astype(x.dtype),
                  "batch", None, "tp"), approximate=True)
    u = constrain(x @ params["in_rnn"].astype(x.dtype), "batch", None, "tp")
    conv_in = u
    u = _causal_conv(u, params["conv_w"].astype(x.dtype),
                     params["conv_b"].astype(x.dtype))

    a, bx = _rglru_gates(params, u)               # (B, S, d_rnn) fp32
    if init_h is not None:
        # fold the carried state into the first step: h_0-contribution
        bx = bx.at[:, 0, :].add(a[:, 0, :] * init_h)

    def combine(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, a2 * b1 + b2

    aa, hh = jax.lax.associative_scan(combine, (a, bx), axis=1)
    h_final = hh[:, -1, :]
    y = constrain((hh.astype(x.dtype) * gelu_branch)
                  @ params["out"].astype(x.dtype), "batch", None, None)
    cache = {"conv": conv_in[:, -(params["conv_w"].shape[0] - 1):, :],
             "h": h_final}
    return y, cache


def rglru_decode_step(params: dict, x: jax.Array, cache: dict,
                      cfg: RGLRUConfig):
    """One-token step. x: (B, 1, D) -> (y (B, 1, D), new cache)."""
    gelu_branch = jax.nn.gelu(x @ params["in_gelu"].astype(x.dtype),
                              approximate=True)
    u_new = x @ params["in_rnn"].astype(x.dtype)   # (B, 1, d_rnn)
    window = jnp.concatenate([cache["conv"], u_new], axis=1)
    w = params["conv_w"].astype(x.dtype)
    u = (jnp.einsum("bkc,kc->bc", window, w)
         + params["conv_b"].astype(x.dtype))[:, None, :]

    a, bx = _rglru_gates(params, u)                # (B, 1, d_rnn)
    h = a[:, 0] * cache["h"] + bx[:, 0]
    y = (h[:, None, :].astype(x.dtype) * gelu_branch) @ params["out"].astype(x.dtype)
    return y, {"conv": window[:, 1:, :], "h": h}
