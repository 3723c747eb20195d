"""Random weights of a dense model from the seed (``bench/families/dense.py``),
in the layout the served model takes.

The benchmark, not the program, makes the weights: ``make_params`` builds
the whole tree on the device in one jitted call, in float32 (the
configuration's master-weight type), and the reference regenerates any
one layer alone with ``layer_params``, which draws the same numbers.
Every leaf has its own key, folded from the seed, the layer and the
leaf's index, so a layer's draw does not depend on how many are made.

Scales keep activations of order one: a projection is normal with
variance ``1 / fan_in``; norm gains are ``1 + N(0, 0.1^2)`` and biases
``N(0, 0.1^2)`` so that the reference has to apply them.  An RMSNorm
gain is stored as its offset from 1, as the program keeps it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.dims import Dims

__all__ = ["seed_key", "make_params", "layer_params", "embed", "head",
           "final_norm"]

_EMBED, _HEAD, _FINAL = 1 << 20, (1 << 20) + 1, (1 << 20) + 2


def seed_key(seed: int) -> np.ndarray:
    """The raw key data of ``seed``: all 64 bits of it, as two uint32."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} is not a whole number below 2**64")
    return np.asarray([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def _key(key_data, *path):
    k = jax.random.wrap_key_data(key_data)
    for p in path:
        k = jax.random.fold_in(k, p)
    return k


def _normal(key_data, path, shape, std):
    return jax.random.normal(_key(key_data, *path), shape, jnp.float32) * std


def _norm(key_data, path, d: Dims):
    g = _normal(key_data, path + (0,), (d.d_model,), 0.1)
    if d.norm == "rmsnorm":
        return {"scale": g}
    return {"scale": 1.0 + g,
            "bias": _normal(key_data, path + (1,), (d.d_model,), 0.1)}


def layer_params(key_data, d: Dims, i) -> dict:
    """Layer ``i``'s weights (``i`` may be traced)."""
    D, H, KV, hd, F = d.d_model, d.n_heads, d.n_kv, d.head_dim, d.d_ff
    w = lambda j, shape, fan_in: _normal(key_data, (i, j), shape,
                                         fan_in ** -0.5)
    p = {
        "ln1": _norm(key_data, (i, 0), d),
        "attn": {"wq": w(1, (D, H, hd), D), "wk": w(2, (D, KV, hd), D),
                 "wv": w(3, (D, KV, hd), D), "wo": w(4, (H, hd, D), H * hd)},
        "ln2": _norm(key_data, (i, 5), d),
    }
    if d.act == "silu":
        p["ffn"] = {"w_gate": w(6, (D, F), D), "w_up": w(7, (D, F), D),
                    "w_down": w(8, (F, D), F)}
    else:
        p["ffn"] = {"w_fc": w(6, (D, F), D), "w_proj": w(8, (F, D), F)}
        if d.mlp_bias:
            p["ffn"]["b_fc"] = _normal(key_data, (i, 9), (F,), 0.1)
            p["ffn"]["b_proj"] = _normal(key_data, (i, 10), (D,), 0.1)
    return p


def embed(key_data, d: Dims):
    """The (vocab, d_model) embedding."""
    return _normal(key_data, (_EMBED,), (d.vocab, d.d_model), 0.02)


def head(key_data, d: Dims):
    """The LM head as (d_model, vocab): the embedding's transpose when
    tied."""
    if d.tie:
        return embed(key_data, d).T
    return _normal(key_data, (_HEAD,), (d.d_model, d.vocab),
                   d.d_model ** -0.5)


def final_norm(key_data, d: Dims):
    return _norm(key_data, (_FINAL,), d)


@functools.partial(jax.jit, static_argnums=1)
def make_params(key_data, d: Dims) -> dict:
    """The whole tree, layers stacked on a leading axis, in one program."""
    layers = jax.vmap(lambda i: layer_params(key_data, d, i))(
        jnp.arange(d.n_layers))
    if d.act == "gelu" and not d.mlp_bias:
        # the program's GELU MLP always adds its biases
        layers["ffn"]["b_fc"] = jnp.zeros((d.n_layers, d.d_ff))
        layers["ffn"]["b_proj"] = jnp.zeros((d.n_layers, d.d_model))
    p = {"embed": embed(key_data, d), "final_norm": final_norm(key_data, d),
         "groups": [[layers]]}
    if not d.tie:
        p["lm_head"] = head(key_data, d)
    return p
