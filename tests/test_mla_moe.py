"""Latent attention (MLA), YaRN rope and the held-share expert layer of
DeepSeek-V2-Lite, at smoke size on the CPU.

The float32 model's decode through the latent cache (absorbed) matches
its full forward pass (expanded); YaRN's frequencies and score scale
match the published formula; the shares of an expert layer held by
eight chips add up to the uncut layer, and a skewed routing drops
nothing.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import MoEConfig
from repro.launch.serve import ProgressiveServer
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import transformer as T
from repro.runtime import telemetry

HI = jax.lax.Precision.HIGHEST


def smoke(**kw):
    return dataclasses.replace(registry.get_smoke_config("deepseek-v2-lite"),
                               **kw)


def test_yarn_frequencies_and_scale_follow_the_formula():
    a = registry.get_config("deepseek-v2-lite").attention
    d = lambda b: 64 * math.log(4096 / (2 * math.pi * b)) / (2 * math.log(1e4))
    lo, hi = math.floor(d(32)), math.ceil(d(1))
    assert (lo, hi) == (10, 23)
    want = []
    for i in range(32):
        f = 10000.0 ** (-2 * i / 64)
        r = 1 - min(max((i - lo) / (hi - lo), 0.0), 1.0)
        want.append(f * (1 - r) / 40 + f * r)
    got = L.yarn_inv_freq(a, 64)
    np.testing.assert_allclose(got, np.float32(want), rtol=1e-6)
    # the fast dims keep their frequency, the slow ones are divided by 40
    assert got[0] == 1.0 and got[lo] == np.float32(want[lo])
    np.testing.assert_allclose(got[hi:], np.float32(want[hi:]), rtol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert L.yarn_mscale(a) == pytest.approx(mscale ** 2, rel=1e-12)
    # without scaling: plain rope frequencies, unscaled scores
    plain = dataclasses.replace(a, rope_factor=1.0)
    np.testing.assert_allclose(L.yarn_inv_freq(plain, 64),
                               1e4 ** (-np.arange(0, 64, 2) / 64), rtol=1e-6)
    assert L.yarn_mscale(plain) == 1.0


def test_rope_with_frequencies_equals_rope_with_theta():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 8))
    pos = jnp.broadcast_to(jnp.arange(5), (2, 5))
    inv = 1e4 ** (-np.arange(0, 8, 2) / 8)
    np.testing.assert_allclose(L.rope(x, pos, 1e4, inv), L.rope(x, pos, 1e4),
                               rtol=1e-5, atol=1e-6)


def test_absorbed_decode_equals_expanded_attention():
    a = smoke().attention
    B, S, H = 2, 7, a.num_heads
    r, nope, rope, dv = (a.kv_lora_rank, a.qk_nope_head_dim,
                         a.qk_rope_head_dim, a.v_head_dim)
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q_nope = jax.random.normal(ks[0], (B, S, H, nope))
    q_pe = jax.random.normal(ks[1], (B, S, H, rope))
    ckv = jax.random.normal(ks[2], (B, S, r + rope))
    w = jax.random.normal(ks[3], (r, H, nope + dv)) / np.sqrt(r)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    with jax.default_matmul_precision("highest"):
        full = L.latent_attention(q_nope, q_pe, ckv, w, pos, pos, a)
        # the last query against a cache padded past its valid slots
        cache = jnp.pad(ckv, ((0, 0), (0, 3), (0, 0)))
        one = L.latent_decode_attention(q_nope[:, -1:], q_pe[:, -1:], cache,
                                        w, a, jnp.full((B,), S))
    assert one.shape == (B, 1, H, dv)
    np.testing.assert_allclose(one[:, 0], full[:, -1], rtol=1e-5, atol=1e-5)


def test_prefill_then_latent_decode_matches_the_full_forward():
    cfg = smoke(compute_dtype="float32")
    params = T.init_params(jax.random.PRNGKey(2), cfg)
    P, G = 6, 5
    seq = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, (2, P + G)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        full, _ = T.forward(params, seq, cfg)
        logits, caches = T.prefill(params, seq[:, :P], cfg, max_len=P + G)
        assert jax.tree.leaves(caches)[0].shape[-2:] == (
            P + G, cfg.attention.kv_lora_rank + cfg.attention.qk_rope_head_dim)
        got = [logits]
        for i in range(G - 1):
            logits, caches = T.decode_step(params, seq[:, P + i:P + i + 1],
                                           caches, jnp.int32(P + i), cfg)
            got.append(logits)
    np.testing.assert_allclose(np.stack(got, 1), full[:, P - 1:P + G - 1],
                               rtol=2e-4, atol=2e-4)


def _moe_cfg():
    return MoEConfig(num_experts=64, top_k=6, d_ff_expert=16, d_ff_shared=24,
                     held_experts=(0, 64))


def _plain_layer(params, x, cfg):
    """The layer as its paper writes it: every held expert on every token,
    weighted by its gate where it is among the token's top-k, else 0."""
    first, n = cfg.held_experts
    g = jax.nn.softmax(jnp.dot(x, params["router"], precision=HI), -1)
    top, idx = jax.lax.top_k(g, cfg.top_k)
    y = 0.0
    for e in range(n):
        ge = jnp.where(idx == first + e, top, 0.0).sum(-1)
        h = (jax.nn.silu(jnp.dot(x, params["we_gate"][e], precision=HI))
             * jnp.dot(x, params["we_up"][e], precision=HI))
        y = y + ge[:, None] * jnp.dot(h, params["we_down"][e], precision=HI)
    sp = params["shared"]
    shared = jnp.dot(jax.nn.silu(jnp.dot(x, sp["w_gate"], precision=HI))
                     * jnp.dot(x, sp["w_up"], precision=HI), sp["w_down"],
                     precision=HI)
    return y + shared, shared


def _share(params, first, n):
    out = dict(params)
    for name in ("we_gate", "we_up", "we_down"):
        out[name] = params[name][first:first + n]
    return out


def test_eight_shares_add_up_to_the_uncut_layer():
    cfg = _moe_cfg()
    D, T_ = 32, 40
    params = moe_lib.init_moe_params(jax.random.PRNGKey(4), D, cfg,
                                     jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (T_, D))
    with jax.default_matmul_precision("highest"):
        whole, whole_pairs = moe_lib.held_moe_block(params, x, cfg)
        want, shared = _plain_layer(params, x, cfg)
        parts, pairs = [], []
        for c in range(8):
            share = dataclasses.replace(cfg, held_experts=(8 * c, 8))
            y, gs = moe_lib.held_moe_block(_share(params, 8 * c, 8), x, share)
            parts.append(y - shared)       # the shared expert counts once
            pairs.append(gs)
    np.testing.assert_allclose(whole, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(sum(parts) + shared, want, rtol=1e-5,
                               atol=1e-5)
    assert whole_pairs.shape == (64,) and int(whole_pairs.sum()) == T_ * 6
    np.testing.assert_array_equal(np.concatenate(pairs), whole_pairs)


@pytest.mark.parametrize("first", [5, 0])
def test_a_skewed_routing_drops_no_token(first):
    """Every token picks the same six experts, 4..9, five or four of
    them held: the capacity path would drop most pairs, the held share
    drops none."""
    cfg = dataclasses.replace(_moe_cfg(), held_experts=(first, 8))
    D, T_ = 16, 64
    params = moe_lib.init_moe_params(jax.random.PRNGKey(6), D, cfg,
                                     jnp.float32)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(7), (T_, D))) + 1.0
    router = params["router"].at[:, 4:10].add(4.0)      # experts 4..9
    params = dict(params, router=router)
    with jax.default_matmul_precision("highest"):
        got, pairs = moe_lib.held_moe_block(params, x, cfg)
        want, _ = _plain_layer(params, x, cfg)
    held = np.arange(first, first + 8)
    np.testing.assert_array_equal(pairs,
                                  np.where((held >= 4) & (held <= 9), T_, 0))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_active_params_count_the_held_share():
    cfg = registry.get_config("deepseek-v2-lite")
    per_layer = 3 * 2048 * 1408
    assert T.active_params(cfg, 10**11) == 10**11 - per_layer * 26 * 58
    cut = dataclasses.replace(
        cfg, num_layers=9, moe=dataclasses.replace(cfg.moe,
                                                   held_experts=(0, 8)))
    # 8 held experts, 6 of 64 picked: 0.75 held experts active a token
    assert T.active_params(cut, 10**10) == (10**10 - per_layer * 8 * 8
                                            + per_layer * 8 * 6 * 8 // 64)
    assert T.block_groups(cut) == [(("dense",), 1), (("moe",), 8)]


@pytest.mark.parametrize("arch", ["deepseek-v2-lite", "yi-6b"])
def test_decode_records_expert_pairs_of_held_layers_only(arch):
    cfg = registry.get_smoke_config(arch)
    params = T.init_params(jax.random.PRNGKey(8), cfg)
    tracer = telemetry.Tracer()
    server = ProgressiveServer(cfg, params, m=2, d=7, tracer=tracer)
    B, P, G = 2, 8, 3
    tokens = jnp.asarray(np.random.default_rng(9).integers(
        0, cfg.vocab_size, (B, P)), jnp.int32)
    logits, caches = server.prefill(tokens, P + G)
    first = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    server.decode(first, caches, P, G)
    ev = [e for e in tracer.events() if e.kind == telemetry.EXPERT_PAIRS]
    if cfg.moe is None:
        assert ev == []
        return
    n_moe = cfg.num_layers - cfg.moe.first_dense_layers
    n_held = cfg.moe.held_experts[1]
    assert len(ev) == G * n_moe * n_held
    for step in range(G):
        for layer in range(n_moe):
            row = [e for e in ev if e.round == step and e.task == layer]
            assert sorted(e.worker for e in row) == list(range(n_held))
            # every expert is held at smoke size: all B * k pairs
            assert sum(e.value for e in row) == B * cfg.moe.top_k
