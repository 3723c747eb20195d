"""Model families: the dense family gives what the harness gave before
families existed, and a family of another kind is added as new files."""

import hashlib
import json

import jax
import numpy as np
import pytest

from bench import control, harness
from bench import weights as W
from bench.dims import Dims
from bench.families import dense
from bench.peaks import peaks_for
from bench.tests import tiny

REPO = tiny.REPO
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())


def _config(name):
    return json.loads((REPO / "bench" / "configs" / f"{name}.json")
                      .read_text())


def _model_config(name):
    from repro.configs.base import AttentionConfig, ModelConfig
    return {
        "yi-6b": ModelConfig(
            name="yi-6b", family="dense", num_layers=8, d_model=4096,
            d_ff=11008, vocab_size=64000,
            attention=AttentionConfig(num_heads=32, num_kv_heads=4,
                                      head_dim=128, rope_theta=5e6),
            activation="silu", norm="rmsnorm", tie_embeddings=False),
        "starcoder2-7b": ModelConfig(
            name="starcoder2-7b", family="dense", num_layers=6,
            d_model=4608, d_ff=18432, vocab_size=49152,
            attention=AttentionConfig(num_heads=36, num_kv_heads=4,
                                      head_dim=128, rope_theta=1e6),
            activation="gelu", norm="layernorm", tie_embeddings=True),
    }[name]


@pytest.mark.parametrize("name", ["yi-6b", "starcoder2-7b"])
def test_dense_model_config(name):
    c = _config(name)
    assert c["reference"] == "dense"
    assert dense.model_config(dense.dims(c)) == _model_config(name)


# request_flops at each cell's traffic, as bench.work counted them before
# the work counts moved behind the family
@pytest.mark.parametrize("cell, want", [
    ("yi6b-chat-decode", 18_245_222_400_000),
    ("starcoder2-code-prefill", 22_687_978_881_024),
])
def test_dense_request_flops(cell, want):
    c = harness.resolve(REPO, BENCH, cell)
    t = c.traffic
    got = harness.family(c).request_flops(c.dims, t["batch"], t["prompt"],
                                          t["gen"])
    assert type(got) is int and got == want


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_checkout(tmp_path_factory.mktemp("families"))


# sha256 of every leaf's dtype, shape and bytes, in tree order, of the
# weights bench.weights.make_params drew for the tiny cells before the
# weights moved behind the family (seed 2**33 + 9, CPU)
@pytest.mark.parametrize("cell, want", [
    ("tiny-chat",
     "2c98d72a6d3e14cf4793b42c8dbed83f451dfcff31147ab315370b3907d39336"),
    ("tiny-code",
     "117044b6e71f406c91d3b3a3ab34a0290ca09c87d8cc95e4f79c6b1126d5bd59"),
])
def test_dense_make_params_bit_equal(root, cell, want):
    c = harness.resolve(root, harness.load_benchmark(root), cell)
    fam = harness.family(c)
    # the family loaded from the checkout draws with bench.weights itself
    assert fam.make_params is W.make_params
    h = hashlib.sha256()
    for a in jax.tree.leaves(fam.make_params(W.seed_key(2**33 + 9), c.dims)):
        a = np.asarray(a)
        h.update(str((a.dtype, a.shape)).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == want


def test_mfu_counts_the_family_work(monkeypatch):
    cell = harness.resolve(REPO, BENCH, "yi6b-chat-decode")
    run = harness.Run(cell=cell, seed=1, seconds=2.0,
                      peaks=peaks_for("TPU v5 lite"))
    run.host = dict(requests=3, batch=16, prompt=256, gen=128, window_s=2.0)
    mfu = harness.reader(cell, "mfu")
    assert mfu(run) == pytest.approx(
        100 * 3 * 18_245_222_400_000 / (2.0 * 197e12), rel=1e-12)

    class Family:
        @staticmethod
        def request_flops(d, batch, prompt, gen):
            return 10**12 * batch

    monkeypatch.setattr(harness, "family", lambda c: Family)
    assert mfu(run) == pytest.approx(100 * 3 * 16e12 / (2.0 * 197e12),
                                     rel=1e-12)


TOY_FAMILY = '''"""A made-up family: the dense block, read from keys that
``bench.dims.Dims.from_config`` does not know."""

from bench.dims import Dims
from bench.families import dense

model_config = dense.model_config
make_params = dense.make_params
request_flops = dense.request_flops


def dims(c):
    heads = c["num_attention_heads"]
    return Dims(name=c["name"], d_model=c["hidden_size"],
                d_ff=c["intermediate_size"],
                n_layers=c["num_hidden_layers"], n_heads=heads,
                n_kv=c["num_key_value_heads"],
                head_dim=c["hidden_size"] // heads, vocab=c["vocab_size"],
                rope_theta=float(c["rope_theta"]),
                eps=float(c["layer_norm_epsilon"]),
                act={"swiglu": "silu"}[c["mlp_hidden_act"]],
                norm="rmsnorm", tie=False, mlp_bias=False)
'''

TOY_REFERENCE = '''"""The made-up family's plain reference: the dense one."""

from bench.reference.dense import MODES, hidden, logits  # noqa: F401
'''


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def test_second_family_added_as_new_files(root):
    """A family module, a reference, a configuration, traffic, limits and
    entries in BENCHMARK.json: no file of the benchmark changes, and the
    cell runs correct under the serve generator, its control not."""
    before = _digests(REPO)
    here = root / "bench"
    (here / "families" / "toy.py").write_text(TOY_FAMILY)
    (here / "reference" / "toy.py").write_text(TOY_REFERENCE)
    c = json.loads((here / "configs" / "tiny-llama.json").read_text())
    for k in ("hidden_act", "rms_norm_eps", "norm_epsilon"):
        c.pop(k, None)
    c.update(name="toy-model", reference="toy", mlp_hidden_act="swiglu",
             layer_norm_epsilon=1e-6)
    with pytest.raises(KeyError):
        Dims.from_config(c)
    (here / "configs" / "toy-model.json").write_text(json.dumps(c))
    t = json.loads((here / "traffic" / "tiny-chat.json").read_text())
    assert t["generator"] == "serve"
    (here / "traffic" / "toy-chat.json").write_text(json.dumps(t))
    (here / "cells" / "toy-chat.json").write_text(
        (here / "cells" / "yi6b-chat-decode.json").read_text())
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "toy-model", "source": "made up",
                         "file": "bench/configs/toy-model.json",
                         "reduced": [], "why": "CPU tests"})
    b["workloads"].append({"name": "toy-chat", "config": "toy-model",
                           "traffic": "toy-chat", "chips": 1, "why": "CPU"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "tiny-chat" in m.get("workloads", ()):
            m["workloads"].append("toy-chat")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    after = _digests(root)
    assert all(after[p] == d for p, d in before.items())

    cell = harness.resolve(root, b, "toy-chat")
    assert cell.dims.eps == 1e-6 and cell.dims.act == "silu"
    res, err = tiny.run_cell(root, "toy-chat", 2**34 + 3, seconds=0)
    assert res["correct"] is True, err
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    rows = control.readings(root, "toy-chat", [2**35 + 11], control_seeds=1,
                            require_tpu=False)
    assert [(r["control"], r["correct"]) for r in rows] == [
        (False, True), (True, False)], rows
