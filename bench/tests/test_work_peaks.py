"""Work counts and peaks against values worked out by hand."""

import json
import pathlib

import pytest

from bench import work
from bench.dims import Dims
from bench.peaks import peaks_for

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def dims(name):
    return Dims.from_config(json.loads((CONFIGS / f"{name}.json").read_text()))


# per layer and token: q, k, v, o projections 2*D*(2H + 2KV)*hd, then the
# MLP: SwiGLU 2*3*D*F, GELU 2*2*D*F
@pytest.mark.parametrize("name, want", [
    ("yi-6b", 2 * 4096 * (64 + 8) * 128 + 6 * 4096 * 11008),
    ("starcoder2-7b", 2 * 4608 * (72 + 8) * 128 + 4 * 4608 * 18432),
])
def test_layer_matmul_flops(name, want):
    assert work.layer_matmul_flops(dims(name)) == want


def test_yi_request_by_hand():
    d = dims("yi-6b")
    assert (d.n_layers, d.head_dim, d.act, d.norm, d.tie) == (
        8, 128, "silu", "rmsnorm", False)
    layer = 346_030_080           # 75_497_472 attention + 270_532_608 MLP
    assert work.layer_matmul_flops(d) == layer
    # prefill of 2 rows of 3 tokens: 8 layers x 3 tokens, causal keys
    # 1 + 2 + 3 = 6 at 4 * 32 * 128 each, one head row per prompt
    want = 2 * (8 * (3 * layer + 4 * 32 * 128 * 6) + 2 * 4096 * 64000)
    assert work.prefill_flops(d, 2, 3) == want
    # decode at position 3: the cache holds 4 keys
    step = 2 * (8 * (layer + 4 * 32 * 128 * 4) + 2 * 4096 * 64000)
    assert work.decode_flops(d, 2, 3) == step
    assert work.request_flops(d, 2, 3, 1) == want + step


def test_starcoder_dims():
    d = dims("starcoder2-7b")
    assert (d.d_model, d.n_heads, d.n_kv, d.head_dim, d.d_ff, d.vocab) == (
        4608, 36, 4, 128, 18432, 49152)
    assert (d.act, d.norm, d.tie, d.mlp_bias, d.eps) == (
        "gelu", "layernorm", True, True, 1e-5)
    assert work.decode_flops(d, 1, 0) == (
        d.n_layers * (work.layer_matmul_flops(d) + 4 * 36 * 128)
        + 2 * 4608 * 49152)


@pytest.mark.parametrize("name, k, v", [("yi-6b", 4096, 64000),
                                        ("starcoder2-7b", 4608, 49152)])
def test_head_work(name, k, v):
    d = dims(name)
    assert (d.d_model, d.vocab) == (k, v)
    ops, nbytes = work.head_work(16, k, v, 2)
    assert ops == 2 * 2 * 16 * k * v
    # two int8 planes + bf16 hidden + two f32 resolutions
    assert nbytes == 2 * k * v + 2 * 16 * k + 4 * 2 * 16 * v
    # at the v5e peaks the head is bound by its bytes
    p = peaks_for("TPU v5 lite")
    assert nbytes / p.hbm_bytes_per_s > ops / p.bf16_flops


def test_v5e_peaks():
    p = peaks_for("TPU v5 lite")
    assert (p.bf16_flops, p.int8_ops, p.hbm_bytes_per_s, p.hbm_bytes) == (
        197e12, 393e12, 819e9, 16e9)
    assert "cloud.google.com" in p.source


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "", "tpu v5 lite"])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for(kind)
