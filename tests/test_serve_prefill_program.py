"""The server's prefill is one jitted program per argument shape.

``ProgressiveServer.prefill`` runs ``T.prefill`` as a program built once
with the server: the same logits and caches as the eager call, compiled
on the first request of each shape (batch, prompt length, ``max_len``,
the extra inputs' shapes) and found again by every later one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.launch.serve import ProgressiveServer
from repro.models import transformer as T
from repro.runtime import telemetry

B, P, G = 2, 8, 4


def prompt(cfg, batch=B, length=P):
    return jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, length)), jnp.int32)


def extras(cfg):
    """The extra inputs ``launch/serve.main`` gives the configuration."""
    out = {}
    if cfg.is_encdec:
        out["audio_embeds"] = jax.random.normal(
            jax.random.PRNGKey(3), (B, cfg.encoder_seq, cfg.d_model)
        ).astype(cfg.cdtype())
    if cfg.num_image_tokens:
        out["extra_embeds"] = jax.random.normal(
            jax.random.PRNGKey(4), (B, cfg.num_image_tokens, cfg.d_model)
        ).astype(cfg.cdtype())
    return out


def close(got, want):
    """Equal dtypes and shapes; values within bfloat16's rounding."""
    assert got.dtype == want.dtype and got.shape == want.shape
    integer = jnp.issubdtype(want.dtype, jnp.integer)
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if integer:
        assert np.abs(got - want).max(initial=0) <= 1
    else:
        scale = max(np.abs(want).max(initial=0), 1e-6)
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2 * scale)


def compiled_in(events, job):
    """The compile stages recorded inside request ``job``'s prefill."""
    pre, = [e for e in events
            if e.kind == telemetry.SERVE_PREFILL and e.job == job]
    return [c for c in events if c.kind == telemetry.COMPILE
            and c.job == job and pre.t <= c.t <= pre.t + pre.dur]


# a dense decoder, the latent-attention expert model, and the two
# configurations that take extra inputs (image and audio embeddings)
@pytest.mark.parametrize("arch", ["yi-6b", "deepseek-v2-lite",
                                  "internvl2-1b", "whisper-tiny"])
def test_program_matches_the_eager_prefill(arch):
    cfg = registry.get_smoke_config(arch)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    server = ProgressiveServer(cfg, params, m=2, d=7)
    tokens, kw = prompt(cfg), extras(cfg)
    logits, caches = server.prefill(tokens, P + G, **kw)
    want, want_caches = T.prefill(server.params, tokens, cfg,
                                  max_len=P + G, **kw)
    close(logits, want)
    got_leaves, got_tree = jax.tree.flatten(caches)
    want_leaves, want_tree = jax.tree.flatten(want_caches)
    assert got_tree == want_tree
    for got, want in zip(got_leaves, want_leaves):
        close(got, want)


@pytest.fixture(scope="module")
def model():
    cfg = registry.get_smoke_config("llama3-8b")
    return cfg, T.init_params(jax.random.PRNGKey(0), cfg)


def test_second_request_of_a_shape_compiles_nothing(model):
    cfg, params = model
    tr = telemetry.Tracer()
    server = ProgressiveServer(cfg, params, m=2, d=7, tracer=tr)
    tokens = prompt(cfg)
    first, _ = server.prefill(tokens, P + G)
    again, _ = server.prefill(tokens, P + G)
    events = tr.events()
    assert compiled_in(events, 0)
    assert compiled_in(events, 1) == []
    np.testing.assert_array_equal(np.asarray(first), np.asarray(again))


@pytest.mark.parametrize("change", ["max_len", "batch"])
def test_a_new_shape_compiles_once(model, change):
    cfg, params = model
    tr = telemetry.Tracer()
    server = ProgressiveServer(cfg, params, m=2, d=7, tracer=tr)
    shapes = {"max_len": (prompt(cfg), 2 * (P + G)),
              "batch": (prompt(cfg, batch=B + 1), P + G)}
    tokens, max_len = shapes[change]
    server.prefill(prompt(cfg), P + G)              # job 0: compiles
    _, caches = server.prefill(tokens, max_len)     # job 1: compiles
    server.prefill(tokens, max_len)                 # job 2: found again
    server.prefill(prompt(cfg), P + G)              # job 3: found again
    events = tr.events()
    assert compiled_in(events, 1)
    assert compiled_in(events, 2) == compiled_in(events, 3) == []
    k = jax.tree.leaves(caches)[0]                  # (layers, B, S, ...)
    assert k.shape[1:3] == (tokens.shape[0], max_len)
