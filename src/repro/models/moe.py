"""Mixture-of-Experts blocks: a top-k router and two ways to dispatch.

``held_moe_block`` is the expert layer of an expert-parallel deployment
(``MoEConfig.held_experts``): the chip holds a contiguous share of the
experts, routes every token over all of them, keeps the (token, expert)
pairs that fall to its own, sorts them by expert and computes them with
one grouped matrix product per projection (``jax.lax.ragged_dot``).  It
has no capacity and drops nothing; holding every expert, it is the whole
layer.  What the absent experts would add is left to the chips that hold
them: no exchange between chips stands in for them here.

``moe_block`` is the GShard capacity path the other MoE configurations
take: tokens are split into groups of
``group_size``; each group dispatches into per-expert capacity buffers
``C = ceil(group_size / E * k * capacity_factor)`` via one-hot einsums.
The dispatch tensor is ``(G, Tg, E, C)`` with G sharded over "data" and E
over "model", so its per-device footprint is
``G/n_data * Tg * E/n_model * C`` — bounded by the *group* size, not the
global token count (the ungrouped (T, E, C) tensor is O(T^2 k / E) and blows
up at 1M tokens; this grouping is why GShard has groups).  Tokens over a
group's capacity are dropped (pass through the residual), standard for
capacity-based MoE.

A shared expert (Qwen2-MoE: 4x1408 fused; Llama4: one 8192) runs densely
alongside the routed experts.

The expert-parallel all-to-all alternative is explored in the perf
hillclimb (EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MoEConfig
from repro.launch.axes import constrain
from repro.models.layers import init_linear, mlp_swiglu

__all__ = ["init_moe_params", "moe_block", "held_moe_block", "router_topk"]

DISPATCH_GROUP = 4096  # tokens per dispatch group (GShard's G)


def init_moe_params(key: jax.Array, d_model: int, cfg: MoEConfig, dtype,
                    extra_dims: tuple[int, ...] = ()) -> dict:
    ks = jax.random.split(key, 7)
    E, F = cfg.num_experts, cfg.d_ff_expert
    # the experts stored here: the held share, else all of them
    n = cfg.held_experts[1] if cfg.held_experts else E
    params = {
        "router": init_linear(ks[0], d_model, E, dtype, extra_dims),
        # experts stacked on a leading expert axis (sharded over "model");
        # distinct "we_*" names so sharding rules can't collide with the
        # dense/shared-expert "w_*" weights.
        "we_gate": init_linear(ks[1], d_model, F, dtype, extra_dims + (n,)),
        "we_up": init_linear(ks[2], d_model, F, dtype, extra_dims + (n,)),
        "we_down": init_linear(ks[3], F, d_model, dtype, extra_dims + (n,)),
    }
    if cfg.d_ff_shared:
        params["shared"] = {
            "w_gate": init_linear(ks[4], d_model, cfg.d_ff_shared, dtype,
                                  extra_dims),
            "w_up": init_linear(ks[5], d_model, cfg.d_ff_shared, dtype,
                                extra_dims),
            "w_down": init_linear(ks[6], cfg.d_ff_shared, d_model, dtype,
                                  extra_dims),
        }
    return params


def router_topk(logits: jax.Array, k: int, normalize: bool = True):
    """Top-k softmax gates (renormalised over the k picks unless
    ``normalize`` is false) + expert indices.

    logits: (..., E) -> gates (..., k) float32, idx (..., k) int32.
    """
    gates_full = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, idx = jax.lax.top_k(gates_full, k)
    if normalize:
        gates = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx


def _shared(params: dict, x: jax.Array) -> jax.Array:
    sp = params["shared"]
    return mlp_swiglu(x, sp["w_gate"].astype(x.dtype),
                      sp["w_up"].astype(x.dtype),
                      sp["w_down"].astype(x.dtype))


def held_moe_block(params: dict, x: jax.Array,
                   cfg: MoEConfig) -> tuple[jax.Array, jax.Array]:
    """The held share of the routed experts, plus the shared expert.

    Routes x (..., D) in float32 over all ``cfg.num_experts``; returns the
    output in x's shape and the grouped product's group sizes: the
    (token, expert) pairs that fell to each held expert, int32 (n,).
    """
    first, n = cfg.held_experts
    D, k = x.shape[-1], cfg.top_k
    xf = x.reshape(-1, D)                                     # (T, D)
    with jax.named_scope("held_experts"):
        logits = jnp.dot(xf.astype(jnp.float32), params["router"],
                         precision=jax.lax.Precision.HIGHEST)
        # DeepSeek-V2 keeps the top-k gates as the softmax gave them
        gates, idx = router_topk(logits, k, normalize=False)  # (T, k)
        # each pair's held expert, n for one held elsewhere: sorted last,
        # past every group, so the grouped products leave it out
        local = idx.reshape(-1) - first
        expert = jnp.where((local >= 0) & (local < n), local, n)
        order = jnp.argsort(expert, stable=True)
        group_sizes = jnp.sum(expert[:, None] == jnp.arange(n), axis=0,
                              dtype=jnp.int32)
        xs = xf[order // k]                                   # (T*k, D)
        wg, wu, wd = (params["we_gate"].astype(x.dtype),
                      params["we_up"].astype(x.dtype),
                      params["we_down"].astype(x.dtype))
        h = (jax.nn.silu(jax.lax.ragged_dot(xs, wg, group_sizes))
             * jax.lax.ragged_dot(xs, wu, group_sizes))
        ys = jax.lax.ragged_dot(h, wd, group_sizes)           # (T*k, D)
        # rows past the groups hold nothing the product defines
        ys = jnp.where((expert[order] < n)[:, None], ys, 0)
        ys = ys[jnp.argsort(order)].reshape(-1, k, D)
        w = jnp.where(expert.reshape(-1, k) < n, gates, 0.0)
        y = jnp.einsum("tkd,tk->td", ys.astype(jnp.float32), w)
    y = y.astype(x.dtype)
    if cfg.d_ff_shared:
        y = y + _shared(params, xf)
    return y.reshape(x.shape), group_sizes


def moe_block(params: dict, x: jax.Array, cfg: MoEConfig,
              group_size: int | None = None) -> jax.Array:
    """Apply the routed-expert FFN to x (..., D); returns the same shape."""
    orig_shape = x.shape
    D = x.shape[-1]
    xf = x.reshape(-1, D)                          # (T, D)
    T = xf.shape[0]
    E, k = cfg.num_experts, cfg.top_k

    if group_size is None:
        group_size = cfg.dispatch_group or DISPATCH_GROUP
    Tg = min(group_size, T)
    if T % Tg:  # shapes in this repo are powers of two; guard anyway
        Tg = int(np.gcd(T, Tg))
    G = T // Tg
    capacity = int(np.ceil(Tg / E * k * cfg.capacity_factor))
    capacity = max(capacity, 2)

    xg = xf.reshape(G, Tg, D)
    # routed in float32 from the float32 router, as held_moe_block routes
    router_logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32),
                               params["router"].astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST)
    gates, idx = router_topk(router_logits, k)                # (G, Tg, k)

    # Position of each (token, choice) inside its expert's group buffer.
    onehot_e = jax.nn.one_hot(idx, E, dtype=jnp.int32)        # (G, Tg, k, E)
    flat = onehot_e.reshape(G, Tg * k, E)
    pos = jnp.cumsum(flat, axis=1) - 1                         # (G, Tg*k, E)
    pos = (pos * flat).sum(-1).reshape(G, Tg, k)               # (G, Tg, k)
    keep = pos < capacity
    gates = jnp.where(keep, gates, 0.0)
    # one_hot(index == capacity) == all-zeros, so dropped tokens vanish.
    pos = jnp.where(keep, pos, capacity)

    dtype = x.dtype
    # Accumulate over the k choices with an unrolled loop (k <= 4) so the
    # (G, Tg, k, E, C) intermediate never materialises -- only the
    # (G, Tg, E, C) dispatch/combine pair is live.
    dispatch = jnp.zeros((G, Tg, E, capacity), dtype)
    combine = jnp.zeros((G, Tg, E, capacity), dtype)
    for kk in range(k):
        oh = (jax.nn.one_hot(idx[..., kk], E, dtype=dtype)[..., None]
              * jax.nn.one_hot(pos[..., kk], capacity,
                               dtype=dtype)[..., None, :])     # (G,Tg,E,C)
        dispatch = dispatch + oh
        combine = combine + oh * gates[..., kk, None, None].astype(dtype)

    dispatch = constrain(dispatch, "batch", None, "tp", None)
    combine = constrain(combine, "batch", None, "tp", None)
    expert_in = jnp.einsum("gtd,gtec->gecd", xg, dispatch)     # (G, E, C, D)
    expert_in = constrain(expert_in, "batch", "tp", None, None)
    wg, wu, wd = (params["we_gate"].astype(dtype),
                  params["we_up"].astype(dtype),
                  params["we_down"].astype(dtype))
    h = (jax.nn.silu(jnp.einsum("gecd,edf->gecf", expert_in, wg))
         * jnp.einsum("gecd,edf->gecf", expert_in, wu))
    expert_out = constrain(jnp.einsum("gecf,efd->gecd", h, wd),
                           "batch", "tp", None, None)
    yg = jnp.einsum("gecd,gtec->gtd", expert_out, combine)     # (G, Tg, D)
    yg = constrain(yg, "batch", None, None)

    yf = yg.reshape(T, D)
    if cfg.d_ff_shared:
        yf = yf + _shared(params, xf)
    return yf.reshape(orig_shape)
