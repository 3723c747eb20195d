"""The latent-attention mixture-of-experts family (DeepSeek-V2-Lite): its
weights in the program's layout, its work counts by hand, the program
against its plain reference, and a tiny cell beside the real ones that
passes its checks while its float8 control fails them."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, harness
from bench import weights as W
from bench.families import mla_moe as F
from bench.reference import mla_moe as ref
from bench.tests import tiny
from bench.trace import Reduction
from repro.models import transformer as T
from repro.runtime import telemetry

REPO = tiny.REPO
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
REAL = "dsv2lite-chat-decode"
#: smoke widths; the router keeps its published 64 outputs and top-6
SMALL = {"hidden_size": 64, "num_attention_heads": 4, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "intermediate_size": 96, "moe_intermediate_size": 32,
         "num_hidden_layers": 3, "vocab_size": 512}


def small_config():
    c = json.loads((REPO / "bench" / "configs" / "deepseek-v2-lite.json")
                   .read_text())
    c.update(SMALL, name="tiny-dsv2")
    return c


def make_checkout(tmp):
    """``tiny.make_checkout``'s checkout with a tiny cell of this family
    added the way a new cell is added: new files and new entries."""
    root = tiny.make_checkout(tmp)
    here = root / "bench"
    (here / "configs" / "tiny-dsv2.json").write_text(
        json.dumps(small_config()))
    t = json.loads((here / "traffic" / "chat-decode-b64.json").read_text())
    t.update(batch=2, prompt=8, gen=4, check_requests=2)
    (here / "traffic" / "tiny-dsv2.json").write_text(json.dumps(t))
    (here / "cells" / "tiny-dsv2.json").write_text(
        (here / "cells" / f"{REAL}.json").read_text())
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny-dsv2", "source": "smoke size",
                         "file": "bench/configs/tiny-dsv2.json",
                         "reduced": [], "why": "CPU tests"})
    b["workloads"].append({"name": "tiny-dsv2", "config": "tiny-dsv2",
                           "traffic": "tiny-dsv2", "chips": 1,
                           "why": "CPU"})
    for m in b["end_to_end"] + b["per_layer"]:
        if REAL in m.get("workloads", ()):
            m["workloads"].append("tiny-dsv2")
    (root / "BENCHMARK.json").write_text(json.dumps(b, indent=1))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_checkout(tmp_path_factory.mktemp("mla_moe"))


def test_the_real_file_keeps_the_published_widths():
    c = harness.resolve(REPO, BENCH, REAL)
    d = c.dims
    assert (d.d_model, d.n_heads, d.kv_rank, d.qk_nope, d.qk_rope,
            d.v_dim) == (2048, 16, 512, 128, 64, 128)
    assert (d.d_ff, d.d_expert, d.d_shared, d.n_experts, d.top_k,
            d.vocab) == (10944, 1408, 2816, 64, 6, 102400)
    assert (d.n_layers, d.n_dense, d.n_held) == (9, 1, 8)
    assert c.config["published"] == {"num_hidden_layers": 27,
                                      "n_routed_experts": 64}
    entry = {e["name"]: e for e in BENCH["configs"]}["deepseek-v2-lite"]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    shapes = jax.eval_shape(F.make_params, W.seed_key(1), d)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 1.25e9 < n < 1.35e9


def test_make_params_has_the_programs_tree():
    d = F.dims(small_config())
    cfg = F.model_config(d)
    want = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    got = jax.eval_shape(F.make_params, W.seed_key(3), d)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.map(lambda x: (x.shape, x.dtype), got) == jax.tree.map(
        lambda x: (x.shape, x.dtype), want)
    # the reference draws a layer alone: the same numbers
    p = F.make_params(W.seed_key(3), d)
    one = F.layer_params(W.seed_key(3), d, 2, True)
    np.testing.assert_array_equal(p["groups"][1][0]["ffn"]["we_up"][1],
                                  one["ffn"]["we_up"])


def test_request_flops_by_hand():
    d = F.dims(small_config())
    D, H, V = 64, 4, 512
    # per token: q 4x24, latent 32+8, out 4x16 ... projections
    attn = 2 * D * (H * 24 + 40 + H * 16)
    dense = attn + 6 * D * 96
    # 64-wide router; 8 of 64 experts held, 6 picks: 3/4 pair a token
    moe = lambda t: (t * (attn + 2 * D * 64 + 6 * D * 64)
                     + 6 * (t * 6 * 8 // 64) * D * 32)
    B, P, G = 2, 4, 2
    pre = (B * P * dense + 2 * moe(B * P)
           + 3 * (2 * B * P * 32 * H * 32         # latent expanded
                  + 2 * B * 10 * H * 40)          # 10 causal keys a row
           + 2 * B * D * V)

    def step(pos):
        return (B * dense + 2 * moe(B) + 3 * (2 * B * H * 32 * 32
                                              + 2 * B * (pos + 1) * H * 72)
                + 2 * B * D * V)

    assert F.request_flops(d, B, P, G) == pre + step(4) + step(5)
    ops, nbytes = F.expert_work(d, 64)
    assert ops == 6 * 48 * D * 32
    assert nbytes == 2 * (3 * 8 * D * 32 + 2 * 48 * D)


def test_program_matches_the_reference_in_prefill_and_decode():
    """In float32, prefill and then decode through the latent cache give
    the reference's logits at every position."""
    d = F.dims(small_config())
    cfg = dataclasses.replace(F.model_config(d), compute_dtype="float32")
    key = W.seed_key(2**33 + 5)
    params = F.make_params(key, d)
    P, G = 6, 4
    seq = jnp.asarray(np.random.default_rng(0).integers(0, d.vocab,
                                                        (2, P + G)),
                      jnp.int32)
    want = ref.logits(key, ref.hidden(key, seq, d), d)
    with jax.default_matmul_precision("highest"):
        logits, caches = T.prefill(params, seq[:, :P], cfg, max_len=P + G)
        got = [logits]
        for i in range(G - 1):
            logits, caches = T.decode_step(params, seq[:, P + i:P + i + 1],
                                           caches, jnp.int32(P + i), cfg)
            got.append(logits)
    np.testing.assert_allclose(np.stack(got, 1), want[:, P - 1:P + G - 1],
                               rtol=1e-4, atol=1e-4)


def test_tiny_cell_passes_while_its_control_fails(root):
    res, err = tiny.run_cell(root, "tiny-dsv2", 2**32 + 17, seconds=0)
    assert res["correct"] is True, err
    assert res["attempted"] >= 2 and res["failed"] == 0
    rows = control.readings(root, "tiny-dsv2", [2**31 + 11],
                            control_seeds=1, require_tpu=False)
    assert [r["control"] for r in rows] == [False, True]
    assert rows[0]["correct"] is True and rows[1]["correct"] is False


def test_expert_readers_on_a_made_up_record(root):
    cell = harness.resolve(root, json.loads(
        (root / "BENCHMARK.json").read_text()), "tiny-dsv2")
    tr = telemetry.profile_tracer()
    tr.drain()
    try:
        # step 0: layer 0 loads (3, 1, 0, 0), layer 1 (1, 1, 1, 1)
        for layer, row in enumerate([(3, 1, 0, 0), (1, 1, 1, 1)]):
            for e, n in enumerate(row):
                tr.emit(telemetry.EXPERT_PAIRS, 1.0, job=0, round=0,
                        task=layer, worker=e, value=n)
        # a step that routed no pair here counts nothing
        tr.emit(telemetry.EXPERT_PAIRS, 1.1, job=0, round=1, task=0,
                worker=0, value=0)
        imbalance = harness.reader(cell, "expert_imbalance")(None)
    finally:
        tr.drain()
    assert imbalance == pytest.approx((3 / 1 + 1) / 2)
    assert harness.reader(cell, "expert_imbalance")(None) is None

    d, peaks = cell.dims, None
    from bench.peaks import PEAKS
    peaks = PEAKS["TPU v5 lite"]
    run = harness.Run(cell=cell, seed=0, seconds=1.0, peaks=peaks)
    run.host = {"requests": 3, "batch": 2, "prompt": 8, "gen": 4}
    run.trace = Reduction(window_s=1.0, busy_s=0.5, busy_by_device={},
                          module_s={}, op_s={"%ragged-dot-none.1 = x": 2e-3,
                                             "ragged-dot-metadata.2": 1e-3,
                                             "fusion.3": 9.0}, gaps=[])

    def least(t):
        ops, nbytes = F.expert_work(d, t)
        return max(ops / peaks.bf16_flops, nbytes / peaks.hbm_bytes_per_s)
    want = 100 * 3 * 2 * (least(16) + 4 * least(2)) / 3e-3
    got = harness.reader(cell, "expert_roofline")(run)
    assert got == pytest.approx(want)
    run.trace.op_s = {"fusion.3": 9.0}
    assert harness.reader(cell, "expert_roofline")(run) is None
