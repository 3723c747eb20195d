"""The plain reference against the program's prefill and decode, on the CPU.

Smoke sizes of both dense blocks; the program computes in float32 here
(its configured bfloat16 would hide a wrong reference inside rounding),
so prefill and every decode step through the cache must agree with the
reference's full forward pass to float32 rounding.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights as W
from bench.dims import Dims
from bench.generators.serve import model_config
from bench.reference import dense

SMOKE = {
    # the program's RMSNorm epsilon is 1e-6, its LayerNorm's 1e-5
    "rms-swiglu": Dims("s1", d_model=64, d_ff=160, n_layers=2, n_heads=4,
                       n_kv=2, head_dim=16, vocab=512, rope_theta=5e6,
                       eps=1e-6, act="silu", norm="rmsnorm", tie=False,
                       mlp_bias=False),
    "ln-gelu-tied": Dims("s2", d_model=64, d_ff=256, n_layers=2, n_heads=4,
                         n_kv=2, head_dim=16, vocab=512, rope_theta=1e6,
                         eps=1e-5, act="gelu", norm="layernorm", tie=True,
                         mlp_bias=True),
}


@pytest.mark.parametrize("block", sorted(SMOKE))
def test_reference_matches_prefill_and_decode(block):
    from repro.models import transformer as T
    d = SMOKE[block]
    cfg = dataclasses.replace(model_config(d), compute_dtype="float32")
    key = W.seed_key(2**40 + 7)
    params = W.make_params(key, d)
    B, P, n = 2, 12, 5
    seq = np.random.default_rng(0).integers(0, d.vocab, (B, P + n))
    got = []
    logits, caches = T.prefill(params, jnp.asarray(seq[:, :P]), cfg,
                               max_len=P + n)
    got.append(logits)
    for i in range(n - 1):
        logits, caches = T.decode_step(
            params, jnp.asarray(seq[:, P + i:P + i + 1]), caches,
            jnp.int32(P + i), cfg)
        got.append(logits)
    got = np.stack([np.asarray(g) for g in got], 1)       # (B, n, V)
    h = dense.hidden(key, seq[:, :P + n - 1], d)[:, P - 1:]
    want = np.asarray(dense.logits(key, h, d))
    assert want.shape == got.shape
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


@pytest.mark.parametrize("block", sorted(SMOKE))
def test_fp8_mode_departs(block):
    d = SMOKE[block]
    key = W.seed_key(3)
    seq = np.random.default_rng(1).integers(0, d.vocab, (2, 16))
    f32 = np.asarray(dense.logits(key, dense.hidden(key, seq, d), d))
    f8 = np.asarray(dense.logits(key, dense.hidden(key, seq, d, "fp8"), d,
                                 "fp8"))
    rel = np.abs(f8 - f32).max() / np.abs(f32).max()
    assert 1e-3 < rel < 0.5


def test_layer_draw_does_not_depend_on_the_stack():
    # the same random bits; the float transform of them is compiled into
    # two different programs, which may round it an ulp apart
    d = SMOKE["ln-gelu-tied"]
    key = W.seed_key(2**63 + 5)
    stacked = W.make_params(key, d)["groups"][0][0]
    for i in range(d.n_layers):
        alone = W.layer_params(key, d, i)
        for path, leaf in jax.tree_util.tree_leaves_with_path(alone):
            s = stacked
            for k in path:
                s = s[k.key]
            np.testing.assert_allclose(np.asarray(s[i]), np.asarray(leaf),
                                       rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("seed", [-1, 2**64])
def test_seed_out_of_range(seed):
    with pytest.raises(ValueError):
        W.seed_key(seed)

