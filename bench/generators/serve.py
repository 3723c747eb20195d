"""Closed-loop batched serving through ``ProgressiveServer``.

Traffic parameters (``bench/traffic/<name>.json``, ``"generator": "serve"``):
``batch`` rows per request, ``prompt`` tokens each, ``gen`` decode steps,
the layered head's ``head_m`` planes of ``head_d`` bits, and
``check_requests``, how many finished requests the check compares.

One request is in flight at a time: a prefill of ``batch`` prompts (its
last-position logits give each row's first token), then ``gen`` greedy
decode steps through the KV cache, each step's token from the on-chip
layered head at full resolution.  Every seed sends the same sizes; the
seed draws the weights and the prompt tokens.

``tokens_per_s`` is every output token of every request begun in the
window (``gen + 1`` per row), over the time from the window's start to
the end of the last of them.

The check: after the window, a sample of finished requests drawn from
the seed is run through the plain float32 reference
(``bench/reference/<config reference>.py``) over each prompt and its
served tokens.  ``logit_gap`` is the widest gap by which a served token's
reference logit lies below the reference's best at its position;
``logit_err`` is the root-mean-square error of the prefill's logits (the
last prompt position, every row and every vocabulary entry) against the
reference's, over the standard deviation of the reference's.  A control
run (``rec.control``) puts the reference in float8 in the program's place
for both: at each position of the same prompts and served tokens its
first token, and its logits at the last prompt position.

Every window holds at least ``check_requests`` requests, so that a run
compares as many as its traffic file says, however short its window.

Nothing here knows the model's kind: the configuration's family
(``bench/families/<config reference>.py``) gives the program's
``ModelConfig`` and the weights, and its reference the check's logits.
"""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.window import Window, clock, span

__all__ = ["run", "compare", "Server"]


class Server:
    """The timed path: one request is a prefill then ``gen`` decode steps."""

    def __init__(self, family, d, traffic: dict, key_data):
        from repro.launch.serve import ProgressiveServer
        self.B, self.P, self.G = (traffic["batch"], traffic["prompt"],
                                  traffic["gen"])
        self.params = family.make_params(key_data, d)
        self.server = ProgressiveServer(family.model_config(d), self.params,
                                        m=traffic["head_m"],
                                        d=traffic["head_d"])
        jax.block_until_ready((self.params, self.server.lm_head))

    def request(self, prompt: np.ndarray):
        """Serve one batch; returns (tokens (B, G+1), the prefill's logits
        (B, V) on the device, prefill s, decode s)."""
        tokens = jnp.asarray(prompt, jnp.int32)
        with span("bench.prefill"):
            t0 = clock()
            logits, caches = self.server.prefill(tokens, self.P + self.G)
            jax.block_until_ready((logits, caches))
            t1 = clock()
        with span("bench.decode"):
            first = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            out, _ = self.server.decode(first, caches, self.P, self.G)
            out = np.asarray(out)
            t2 = clock()
        served = np.concatenate([np.asarray(first), out], axis=1)
        return served, logits, t1 - t0, t2 - t1

    def close(self) -> None:
        self.server.close()
        del self.server, self.params


def _prompt(rng, traffic, vocab):
    return rng.integers(0, vocab, (traffic["batch"], traffic["prompt"]),
                        dtype=np.int64)


def compare(ref, d, key_data, prompt: np.ndarray, served: np.ndarray,
            first_logits: np.ndarray, *,
            control: bool = False) -> tuple[float, float]:
    """``(logit_gap, logit_err)`` of one finished request, by the plain
    reference module ``ref`` (``harness.reference``) at sizes ``d``.

    ``served`` (B, G+1) are the tokens served after ``prompt`` (B, P), and
    ``first_logits`` (B, V) the prefill's logits at the last prompt
    position.  With ``control`` the float8 reference stands in for both.
    """
    P, n = prompt.shape[1], served.shape[1]
    seq = jnp.asarray(np.concatenate([prompt, served[:, :-1]], 1), jnp.int32)

    def logits(mode):
        h = ref.hidden(key_data, seq, d, mode)[:, P - 1:P - 1 + n]
        return ref.logits(key_data, h, d, mode)

    want = logits("f32")                                    # (B, n, V)
    if control:
        low = logits("fp8")
        served, first_logits = jnp.argmax(low, -1), low[:, 0]
        del low
    best = want.max(-1)
    gap = best - jnp.take_along_axis(want, jnp.asarray(served)[..., None],
                                     -1)[..., 0]
    w0 = want[:, 0]
    err = (jnp.sqrt(jnp.mean((jnp.asarray(first_logits, jnp.float32) - w0)
                             ** 2)) / jnp.std(w0))
    return float(gap.max()), float(err)


def run(rec, devices, *, t_start: float, trace_dir=None) -> None:
    from bench import harness
    cell, tr, d = rec.cell, rec.cell.traffic, rec.cell.dims
    key_data = W.seed_key(rec.seed)
    srv = Server(harness.family(cell), d, tr, key_data)
    # warm every shape the window uses with one request of its own prompt
    srv.request(_prompt(np.random.default_rng([rec.seed, 1]), tr, d.vocab))

    rng = np.random.default_rng([rec.seed, 0])
    done, pre_s, dec_s = [], [], []
    win = Window(trace_dir)
    rec.setup_s = clock() - t_start
    with win:
        while (clock() - win.start < rec.seconds
               or len(done) < tr["check_requests"]):
            prompt = _prompt(rng, tr, d.vocab)
            served, first_logits, tp, td = srv.request(prompt)
            done.append((prompt, served, first_logits))
            pre_s.append(tp)
            dec_s.append(td)
    rec.device = harness.device_info(devices)
    rec.window_compiles = win.compiles
    window_s = win.end - win.start
    n_tok = sum(s.size for _, s, _ in done)
    rec.end_to_end = {"tokens_per_s": n_tok / window_s,
                      "setup_s": rec.setup_s}
    rec.host = {"window_s": window_s, "requests": len(done),
                "prefill_s": pre_s, "decode_s": dec_s,
                "decode_steps": len(done) * tr["gen"],
                "batch": tr["batch"], "prompt": tr["prompt"],
                "gen": tr["gen"]}
    rec.attempted = len(done)
    ok = [s.shape == (tr["batch"], tr["gen"] + 1)
          and bool(((s >= 0) & (s < d.vocab)).all()) for _, s, _ in done]
    rec.failed = ok.count(False)
    print(f"[serve] {cell.name}: {len(done)} requests, {n_tok} tokens in "
          f"{window_s:.3f} s; prefill median "
          f"{np.median(pre_s) * 1e3:.3f} ms, decode step median "
          f"{np.median(dec_s) / tr['gen'] * 1e3:.3f} ms; set-up "
          f"{rec.setup_s:.3f} s; {win.compiles} programs lowered in the "
          f"window", flush=True)

    pick = np.random.default_rng([rec.seed, 2]).choice(
        len(done), size=min(tr["check_requests"], len(done)), replace=False)
    checked = [(done[i][0], done[i][1], np.asarray(done[i][2], np.float32))
               for i in pick]
    srv.close()
    del srv, done
    gc.collect()
    t0 = clock()
    ref = harness.reference(cell)
    readings = [compare(ref, d, key_data, *c, control=rec.control)
                for c in checked]
    print(f"[serve] {'float8 control' if rec.control else 'reference'} "
          f"over {len(checked)} request(s), "
          f"{sum(c[1].size for c in checked)} served tokens: "
          f"{clock() - t0:.3f} s", flush=True)
    lim = cell.limits
    rec.checks = [
        ("logit_gap", max(r[0] for r in readings), lim["logit_gap"]),
        ("logit_err", max(r[1] for r in readings), lim["logit_err"]),
        ("failed_requests", float(rec.failed), 0.0)]
    if trace_dir is not None:
        rec.trace = win.reduce()
        rec.device.update(busy_s=rec.trace.busy_s,
                          window_s=rec.trace.window_s)
