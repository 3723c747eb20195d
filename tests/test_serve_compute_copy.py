"""The server's compute-dtype copy of the weights: made once, bit-exact.

``ProgressiveServer`` casts each leaf that the model reads only through a
cast to the compute dtype once, when it is built, and serves from that
copy.  Served tokens and logits must be the very bits that the per-step
cast of the float32 masters gives, in every model family, and the decode
step's program must no longer cast a stacked weight.
"""

import collections

import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import AttentionConfig, ModelConfig
from repro.launch.serve import ProgressiveServer
from repro.models import transformer as T

B, P, G = 2, 8, 3


def masters(cfg, seed=0):
    """float32 master weights with no leaf a bfloat16 value: the model's
    zero biases and unit norm gains would survive any cast unchanged."""
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree.unflatten(treedef, [
        x + (0.05 * jax.random.normal(k, x.shape)).astype(x.dtype)
        for k, x in zip(keys, leaves)])


def inputs(cfg):
    """A prompt and the extra inputs ``launch/serve.main`` gives."""
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (B, P)), jnp.int32)
    extras = {}
    if cfg.is_encdec:
        extras["audio_embeds"] = jax.random.normal(
            jax.random.PRNGKey(3), (B, cfg.encoder_seq, cfg.d_model)
        ).astype(cfg.cdtype())
    if cfg.num_image_tokens:
        extras["extra_embeds"] = jax.random.normal(
            jax.random.PRNGKey(4), (B, cfg.num_image_tokens, cfg.d_model)
        ).astype(cfg.cdtype())
    return tokens, extras


def bits(x):
    return np.asarray(x).view(f"u{np.asarray(x).dtype.itemsize}")


# the yi-6b and starcoder2-7b smoke configurations are the two benchmark
# cells' models at the tiny cells' widths; recurrentgemma-9b reads its
# RG-LRU gate weights in float32, and deepseek-v2-lite its router, which
# the copy must leave alone
@pytest.mark.parametrize("arch", sorted(registry.ARCH_IDS))
def test_served_bits_equal_the_masters(arch):
    cfg = registry.get_smoke_config(arch)
    params = masters(cfg)
    server = ProgressiveServer(cfg, params, m=2, d=7)
    assert server.compute_copy_bytes > 0
    tokens, extras = inputs(cfg)

    logits, caches = server.prefill(tokens, P + G, **extras)
    want, ref_caches = T.prefill(params, tokens, cfg, max_len=P + G,
                                 **extras)
    np.testing.assert_array_equal(bits(logits), bits(want))

    first = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    served, _ = server.decode(first, caches, P, G)

    # the same steps by hand: the masters and the copy side by side
    _, caches = server.prefill(tokens, P + G, **extras)
    tok, ref = first, []
    for i in range(G):
        pos = jnp.int32(P + i)
        h_ref, ref_caches = server.hidden_step(params, tok, ref_caches,
                                               pos)[:2]
        h, caches = server.hidden_step(server.params, tok, caches, pos)[:2]
        np.testing.assert_array_equal(bits(h), bits(h_ref))
        tok = jnp.argmax(server.head_series(h_ref)[-1], -1).astype(
            jnp.int32)[:, None]
        ref.append(tok)
    np.testing.assert_array_equal(np.asarray(served),
                                  np.asarray(jnp.concatenate(ref, 1)))


def weight_casts(fn, params, *args):
    """The key paths of the leaves of ``params`` whose values ``fn``'s
    jaxpr converts to bfloat16, one entry for each such conversion.

    A leaf is followed into the programs, scans and calls it is passed
    to, which take their operands in order."""
    closed = jax.make_jaxpr(fn)(params, *args)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    found = []

    def walk(jaxpr, origin):
        for eqn in jaxpr.eqns:
            src = [origin.get(v) if isinstance(v, jax.extend.core.Var)
                   else None for v in eqn.invars]
            if (eqn.primitive.name == "convert_element_type"
                    and eqn.params["new_dtype"] == jnp.bfloat16
                    and src[0] is not None):
                found.append(src[0])
            for sub in eqn.params.values():
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns") and len(sub.invars) == len(src):
                    walk(sub, {v: s for v, s in zip(sub.invars, src)
                               if s is not None})

    walk(closed.jaxpr, {v: jax.tree_util.keystr(path)
                        for v, (path, _) in zip(closed.jaxpr.invars, flat)})
    return found


def test_copy_takes_the_casts_out_of_the_decode_step():
    cfg = registry.get_smoke_config("yi-6b")
    params = masters(cfg)
    server = ProgressiveServer(cfg, params, m=2, d=7)
    tokens, _ = inputs(cfg)
    _, caches = server.prefill(tokens, P + G)
    args = (tokens[:, -1:], caches, jnp.int32(P))

    stacked = [jax.tree_util.keystr(path) for path, x
               in jax.tree_util.tree_flatten_with_path(params)[0]
               if x.ndim >= 3]
    assert len(stacked) == 7                 # q, k, v, o; gate, up, down
    cast = collections.Counter(weight_casts(server.hidden_step, params,
                                            *args))
    assert cast == {path: 1 for path in stacked}
    assert weight_casts(server.hidden_step, server.params, *args) == []

    copy = jax.tree_util.tree_flatten_with_path(server.params)[0]
    assert server.compute_copy_bytes == sum(
        x.nbytes for _, x in copy if x.dtype == jnp.bfloat16)
    assert {jax.tree_util.keystr(p) for p, x in copy
            if x.dtype == jnp.bfloat16} == set(stacked) | {
        "['embed']", "['lm_head']"}
    # the masters are the caller's: still float32, still there
    for x in jax.tree.leaves(params):
        assert x.dtype == jnp.float32 and not x.is_deleted()


def test_float32_compute_serves_the_masters():
    cfg = ModelConfig(
        name="t", family="dense", num_layers=2, d_model=32, d_ff=64,
        vocab_size=128, compute_dtype="float32",
        attention=AttentionConfig(num_heads=2, num_kv_heads=1,
                                  head_dim=16))
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    server = ProgressiveServer(cfg, params, m=3, d=5)
    assert server.compute_copy_bytes == 0
    assert server.params is params


def test_copy_keeps_the_router_and_casts_latent_and_expert_matrices():
    cfg = registry.get_smoke_config("deepseek-v2-lite")
    params = masters(cfg)
    server = ProgressiveServer(cfg, params, m=2, d=7)
    dtypes = {jax.tree_util.keystr(p): x.dtype for p, x
              in jax.tree_util.tree_flatten_with_path(server.params)[0]}
    moe = "['groups'][1][0]"
    assert dtypes[moe + "['ffn']['router']"] == jnp.float32
    for leaf in ("['attn']['wq']", "['attn']['wkv_a']", "['attn']['wkv_b']",
                 "['attn']['wo']", "['ffn']['we_gate']", "['ffn']['we_up']",
                 "['ffn']['we_down']", "['ffn']['shared']['w_down']"):
        assert dtypes[moe + leaf] == jnp.bfloat16, leaf
    # norm gains, the latent's among them, stay float32
    assert dtypes[moe + "['attn']['kv_norm']['scale']"] == jnp.float32
    assert dtypes["['groups'][0][0]['ffn']['w_gate']"] == jnp.bfloat16
    # the held-share layer reads the router in float32: no cast left
    tokens, _ = inputs(cfg)
    _, caches = server.prefill(tokens, P + G)
    args = (tokens[:, -1:], caches, jnp.int32(P))
    assert weight_casts(server.hidden_step, server.params, *args) == []
    assert not any("router" in path for path in
                   weight_casts(server.hidden_step, params, *args))
