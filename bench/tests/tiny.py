"""A checkout with tiny cells beside the real ones, for CPU tests.

The tiny cells are added the way a new cell is added: new configuration,
traffic and limits files and new entries in ``BENCHMARK.json``; no file
of the benchmark is edited.  Their limits are the real cells' limits.
"""

import contextlib
import io
import json
import pathlib
import shutil
import time

REPO = pathlib.Path(__file__).resolve().parents[2]

TINY = {
    # name: (real cell whose limits it takes, config, traffic)
    "tiny-chat": ("yi6b-chat-decode", "tiny-llama", "tiny-chat"),
    "tiny-code": ("starcoder2-code-prefill", "tiny-starcoder", "tiny-code"),
}


def make_checkout(tmp: pathlib.Path) -> pathlib.Path:
    root = tmp / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    here = root / "bench"
    small = {"hidden_size": 64, "num_attention_heads": 4,
             "num_key_value_heads": 2, "num_hidden_layers": 2,
             "vocab_size": 512}
    for name, real in (("tiny-llama", "yi-6b"),
                       ("tiny-starcoder", "starcoder2-7b")):
        c = json.loads((here / "configs" / f"{real}.json").read_text())
        c.update(small, name=name, intermediate_size=160)
        (here / "configs" / f"{name}.json").write_text(json.dumps(c))
        b["configs"].append({"name": name, "source": "smoke size",
                             "file": f"bench/configs/{name}.json",
                             "reduced": [], "why": "CPU tests"})
    traffic = {
        "tiny-chat": ("chat-decode", dict(batch=2, prompt=8, gen=4,
                                          check_requests=2)),
        "tiny-code": ("code-prefill", dict(batch=2, prompt=16, gen=16,
                                           check_requests=2)),
    }
    for name, (real, change) in traffic.items():
        t = json.loads((here / "traffic" / f"{real}.json").read_text())
        t.update(change)
        (here / "traffic" / f"{name}.json").write_text(json.dumps(t))
    for cell, (real, config, tr) in TINY.items():
        shutil.copy(here / "cells" / f"{real}.json",
                    here / "cells" / f"{cell}.json")
        b["workloads"].append({"name": cell, "config": config,
                               "traffic": tr, "chips": 1, "why": "CPU"})
        for m in b["end_to_end"] + b["per_layer"]:
            if real in m.get("workloads", ()):
                m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(b, indent=1))
    return root


def run_cell(root, cell, seed, *, seconds=1.5, trace=0):
    """One run on the CPU; returns (result line as a dict, stderr)."""
    from bench import run
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                  str(seconds), "--trace", str(trace)], require_tpu=False,
                 root=root, t_start=time.monotonic())
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()
