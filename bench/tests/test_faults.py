"""A whole run with the timed path broken underneath must read incorrect.

The tiny cells skip the harness's look for a chip and drive the rest of
a run on the CPU: the check that decides ``correct`` sees each fault the
cell can have, and passes the sound run.
"""

import jax.numpy as jnp
import pytest

from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_checkout(tmp_path_factory.mktemp("faults"))


def alter_served_token(monkeypatch):
    from repro.launch.serve import ProgressiveServer
    decode = ProgressiveServer.decode

    def altered(self, *a, **kw):
        out, stats = decode(self, *a, **kw)
        return out.at[0, -1].set((out[0, -1] + 1) % self.cfg.vocab_size), \
            stats
    monkeypatch.setattr(ProgressiveServer, "decode", altered)


def keep_the_cache(monkeypatch):
    """Each decode step returns the KV cache it was given, unchanged."""
    from repro.launch.serve import ProgressiveServer
    init = ProgressiveServer.__init__

    def patched(self, *a, **kw):
        init(self, *a, **kw)
        step = self.hidden_step
        self.hidden_step = lambda p, tok, caches, pos: (
            step(p, tok, caches, pos)[0], caches)
    monkeypatch.setattr(ProgressiveServer, "__init__", patched)


def leave_out_half_the_batch(monkeypatch):
    """Prefill runs the first half of the rows and serves it for all."""
    from repro.launch.serve import ProgressiveServer
    prefill = ProgressiveServer.prefill

    def half(self, tokens, max_len, **kw):
        h = tokens[:(tokens.shape[0] + 1) // 2]
        return prefill(self, jnp.concatenate([h, h])[:tokens.shape[0]],
                       max_len, **kw)
    monkeypatch.setattr(ProgressiveServer, "prefill", half)


@pytest.mark.parametrize("cell, fault", [
    ("tiny-chat", None), ("tiny-chat", alter_served_token),
    ("tiny-chat", keep_the_cache), ("tiny-chat", leave_out_half_the_batch),
    ("tiny-code", None), ("tiny-code", alter_served_token),
    ("tiny-code", keep_the_cache), ("tiny-code", leave_out_half_the_batch),
])
def test_fault_reads_incorrect(root, monkeypatch, cell, fault):
    if fault is not None:
        fault(monkeypatch)
    # a window of no length still serves the requests a run compares, and
    # always the same ones
    res, err = tiny.run_cell(root, cell, 2**32 + 17, seconds=0)
    assert res["correct"] is (fault is None), err
    assert res["attempted"] >= 1 and res["failed"] == 0
    verdicts = [line for line in err.splitlines()
                if line.startswith("[check] ")]
    assert len(verdicts) == len(res["checks"])
    assert any(v.endswith("FAIL") for v in verdicts) is (fault is not None)
