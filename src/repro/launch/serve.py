"""Batched serving driver with deadline-bounded progressive resolution.

The paper's §IV deadline experiment at the LM-head (docs/paper-map.md):
each decode step has a budget, logits are produced resolution-by-
resolution MSB-first, and when the budget expires the server releases
the best resolution computed so far instead of nothing.

Two budget modes, one release contract:

* ``layer_budget`` — the budget is a *resolution count* (deterministic,
  test-friendly): the jitted on-chip head series
  (:func:`repro.core.progressive.resolution_series`) computes ``m``
  plane-partial logits and the step releases layer ``budget``.
* ``deadline_ms`` — the budget is wall-clock, and the step IS a runtime
  job: the head matmul ``hidden @ W`` is submitted to a
  :class:`~repro.runtime.gateway.ServingGateway` (thread-backend fleet,
  started on first use) with the step's deadline and a guaranteed
  minimum of resolution 0, so all deadline logic — §IV termination,
  best-ready release, guaranteed-minimum rounds — flows through the
  runtime's own machinery rather than a serving-side controller.  Both
  operands are digit-decomposed, so the step walks the full
  ``L = 2m - 1`` layered resolutions of Definition 1.

The historical ``PlaneBudgetController`` (a serving-local EWMA deadline
predictor) is gone: ``launch/serve.py`` no longer owns any deadline
controller.

The prefill is one jitted program, ``jit_prefill``, built with the
server and compiled once per argument shape (batch, prompt length,
``max_len``, the extra inputs' shapes); every later request of those
shapes runs it without tracing or lowering anything.  Its name keeps it
apart from the head's ``jit__lambda`` in the benchmark's trace reduction.
Inside one program the TPU's compiler computes the LM head of
``transformer.prefill`` at the last prompt position alone, the one it
returns; run eagerly, or on the CPU, it computes every position.

Each call and each decode step is a :func:`repro.runtime.telemetry.span`
(``serve.prefill``, ``serve.decode``, ``serve.step``, ``serve.hidden_step``,
``serve.head``) on the profiler's timeline.  While one records (a tracer
given to the server, or a profile being taken) the compile stages inside
it and each step's ``release`` — the time its token is ready on the
device, stamped by a watcher thread — are recorded too, and, for a model
with held-share expert layers, the step's ``expert_pairs``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import queue
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.configs.base import ModelConfig
from repro.core import progressive
from repro.launch import compile_cache
from repro.models import transformer as T
from repro.models.layers import apply_norm
from repro.runtime import RuntimeConfig, ServingGateway, telemetry

__all__ = ["ProgressiveServer", "ServeStats", "main"]


@dataclasses.dataclass
class ServeStats:
    steps: int = 0
    full_resolution: int = 0
    released_at_layer: Optional[list] = None
    #: the release scale: ``m`` head planes (layer_budget / unbudgeted
    #: mode) or ``2m - 1`` layered resolutions (deadline_ms mode)
    resolutions: int = 0
    #: measured head-service seconds per step (deadline_ms mode only) —
    #: the calibration signal for deadline-sizing tests
    head_service_seconds: Optional[list] = None

    def __post_init__(self):
        if self.released_at_layer is None:
            self.released_at_layer = []
        if self.head_service_seconds is None:
            self.head_service_seconds = []


class _RuntimeHead:
    """The LM head as runtime jobs: one warm thread-backend gateway,
    each decode step one deadline-bounded layered job.

    ``hidden @ W`` is submitted as ``a.T @ b`` with ``a = hidden.T``
    (so the coded split needs ``n2 | vocab``), a per-step absolute
    deadline, and ``min_resolution=0`` — the runtime guarantees
    resolution 0 even past the deadline, the §IV release-something
    contract the old plane controller hand-rolled.

    Only W is split (``n1 = 1``): splitting the few-column ``hidden``
    too would copy every coded W block ``n1`` times and raise the
    recovery threshold ``k = n1 * n2``, and with it the decode's
    condition number.
    """

    def __init__(self, w: np.ndarray, m: int, d: int):
        vocab = w.shape[1]
        n2 = next(n for n in (8, 4, 2, 1) if vocab % n == 0)
        cfg = RuntimeConfig(mu=(500.0, 500.0, 500.0), arrival_rate=1000.0,
                            n1=1, n2=n2, omega=1.0, m=m, d=d,
                            straggler="none", backend="thread")
        self.w = np.asarray(w)
        self.num_layers = cfg.num_layers
        self.gateway = ServingGateway(cfg, admission="none").start()

    def step(self, hidden: np.ndarray,
             deadline_s: float) -> tuple[np.ndarray, int, float]:
        """One head matmul under a deadline; returns
        ``(logits, released_resolution, service_seconds)``."""
        ticket = self.gateway.submit(hidden.T, self.w,
                                     deadline=max(deadline_s, 1e-6),
                                     min_resolution=0)
        ticket.wait()
        lr = ticket.result
        rel = ticket.released_resolution
        if rel < 0:
            # deadline fired before even resolution 0 landed; the
            # guaranteed-minimum rounds still finish it — block for the
            # res-0 value, the step must release *something*
            lr.wait_resolution(0)
            rel = 0
        svc = (0.0 if lr.service_started_at is None
               or lr.released_at is None
               else lr.released_at - lr.service_started_at)
        return np.asarray(lr.resolution(rel)), rel, svc

    def close(self) -> None:
        self.gateway.stop()


#: stacked matrices a model reads in float32, never through a cast to
#: the compute dtype: RG-LRU's gate weights (``models/rglru.py``) and the
#: expert router, which both MoE paths read in float32 (``models/moe.py``)
_READ_IN_FLOAT32 = frozenset({"w_a", "w_x", "router"})


def _served_in_compute_dtype(path, leaf) -> bool:
    """Whether the model reads ``leaf`` only as ``leaf.astype(cdtype)``.

    That holds for the float32 embedding, untied head and matrices
    stacked on a layer axis (rank 3 and up), bar
    :data:`_READ_IN_FLOAT32`.  Rank-2 leaves stay float32: the MLP
    biases share that rank with stacked norm gains and recurrence
    constants, which the models read in float32.
    """
    name = getattr(path[-1], "key", None)
    return leaf.dtype == jnp.float32 and (
        name in ("embed", "lm_head")
        or (leaf.ndim >= 3 and name not in _READ_IN_FLOAT32))


def _compute_copy(params: dict, cdtype) -> tuple[dict, int]:
    """``(params with each leaf the model only casts to cdtype cast once,
    bytes of the cast leaves)``.

    The values are the ones the model's per-step ``astype`` makes, so
    serving from the copy is bit-exact; every other leaf is the caller's
    own array.  With float32 compute the caller's tree comes back.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    pick = [i for i, (path, leaf) in enumerate(flat)
            if _served_in_compute_dtype(path, leaf)]
    if jnp.dtype(cdtype) == jnp.float32 or not pick:
        return params, 0
    leaves = [leaf for _, leaf in flat]
    cast = jax.jit(lambda xs: [x.astype(cdtype) for x in xs])(
        [leaves[i] for i in pick])
    for i, x in zip(pick, cast):
        leaves[i] = x
    return (jax.tree_util.tree_unflatten(treedef, leaves),
            sum(x.nbytes for x in cast))


def _layered_head(lin, hidden):
    with jax.named_scope("layered_head"):
        return progressive.resolution_series(lin,
                                             hidden.astype(jnp.float32))


class _ReleaseWatcher:
    """Stamps a ``release`` for each decode step when its token is ready
    on the device, and then copies the step's expert group sizes, if it
    has any, into one ``expert_pairs`` event per MoE layer and held
    expert.  One thread waits on the steps' tokens in order, so the
    serving thread never does."""

    def __init__(self, tracer: telemetry.Tracer, job: int):
        self._steps: queue.SimpleQueue = queue.SimpleQueue()
        self._thread = threading.Thread(target=self._run,
                                        args=(tracer, job),
                                        name="serve-release", daemon=True)

    def __enter__(self) -> "_ReleaseWatcher":
        self._thread.start()
        return self

    def put(self, step: int, release: int, tok, pairs=None) -> None:
        self._steps.put((step, release, tok, pairs))

    def _run(self, tracer: telemetry.Tracer, job: int) -> None:
        while (item := self._steps.get()) is not None:
            step, release, tok, pairs = item
            tok.block_until_ready()
            t = telemetry.clock()
            tracer.emit(telemetry.RELEASE, t, job=job, round=step,
                        value=float(release))
            if pairs is None:
                continue
            for layer, row in enumerate(np.asarray(pairs).tolist()):
                for expert, n in enumerate(row):
                    tracer.emit(telemetry.EXPERT_PAIRS, t, job=job,
                                round=step, task=layer, worker=expert,
                                value=float(n))

    def __exit__(self, *exc) -> None:
        self._steps.put(None)
        self._thread.join()


class ProgressiveServer:
    """Greedy batched decoding with a layered LM head.

    ``hidden_step(params, token, caches, pos)`` (jitted) is one decode step
    up to the final norm, returning ``(hidden (B, D), caches)``, and for a
    model with held-share expert layers also the step's group sizes,
    ``(MoE layers, held experts)`` int32 (``transformer.decode_layers``);
    ``head_series(hidden)`` (jitted) is the on-chip head's ``m``
    MSB-first resolutions, ``(m, B, V)`` float32.  :meth:`prefill` runs
    ``transformer.prefill`` as the jitted ``jit_prefill``, one program per
    argument shape, with ``max_len`` static and the weights an argument.

    ``tracer`` records the serve path's spans without a profiler (see the
    module's docstring); each :meth:`prefill` begins a new request id
    (the events' ``job``), which the :meth:`decode` after it carries.

    ``params`` are the master weights, which the server neither changes
    nor frees.  It serves from ``self.params``: a copy in which every
    leaf the model reads only through a cast to ``cfg.cdtype()`` is cast
    once, here, instead of in every prefill and decode step; the head's
    planes and the deadline head are made from the masters.
    ``compute_copy_bytes`` counts the cast leaves' bytes (0 with float32
    compute, where ``self.params`` is ``params``).
    """

    def __init__(self, cfg: ModelConfig, params: dict, *, m: int = 2,
                 d: int = 7, tracer: Optional[telemetry.Tracer] = None):
        self.cfg = cfg
        self.tracer = tracer
        self._job = -1
        w = (params["embed"].T if cfg.tie_embeddings
             else params["lm_head"]).astype(jnp.float32)
        self.lm_head = progressive.make_layered_linear(w, m=m, d=d)
        self._head_w = w
        self.params, self.compute_copy_bytes = _compute_copy(params,
                                                             cfg.cdtype())
        self.m = m
        self.d = d
        self._runtime_head: Optional[_RuntimeHead] = None

        def hidden(params, token, caches, pos):
            """decode_step up to the final norm, not the logits."""
            x, caches, pairs = T.decode_layers(params, token, caches, pos,
                                               cfg)
            x = apply_norm(cfg.norm, x, params["final_norm"])[:, 0, :]
            # a model without held-share layers keeps the two outputs its
            # callers (and stand-ins for the step) unpack
            return (x, caches) if pairs is None else (x, caches, pairs)

        def hidden_step(params, token, caches, pos):
            with jax.named_scope("hidden_step"):
                return hidden(params, token, caches, pos)

        def prefill(params, tokens, extras, max_len):
            return T.prefill(params, tokens, cfg, max_len=max_len, **extras)

        # the programs' names (``jit_prefill``, ``jit_hidden_step``,
        # ``jit__lambda``) are what the benchmark's trace reduction finds
        # them by
        self._prefill = jax.jit(prefill, static_argnames="max_len")
        self.hidden_step = jax.jit(hidden_step)
        # the head's planes are an argument, not a closure: captured, they
        # would be baked into the program as constants (half a GB at a
        # 4096 x 64000 head)
        series = jax.jit(lambda lin, h: _layered_head(lin, h))
        self.head_series = functools.partial(series, self.lm_head)

    def close(self) -> None:
        """Stop the runtime-head gateway fleet (idempotent)."""
        head, self._runtime_head = self._runtime_head, None
        if head is not None:
            head.close()

    def __enter__(self) -> "ProgressiveServer":
        return self

    def __exit__(self, *exc) -> None:
        del exc
        self.close()

    def prefill(self, tokens, max_len: int, **extras):
        self._job += 1
        with telemetry.span(telemetry.SERVE_PREFILL, "serve.prefill",
                            self.tracer, job=self._job):
            return self._prefill(self.params, tokens, extras,
                                 max_len=max_len)

    def _head(self, hidden, layer_budget, deadline_ms, stats: ServeStats):
        """The step's logits ``(B, V)`` and the resolution released."""
        if deadline_ms is not None:
            # the step is a runtime job: deadline release, best-ready
            # resolution, and the guaranteed res-0 minimum all come
            # from the runtime's §IV machinery
            if self._runtime_head is None:
                self._runtime_head = _RuntimeHead(
                    np.asarray(self._head_w), self.m, self.d)
            logits_np, rel, svc = self._runtime_head.step(
                np.asarray(hidden, np.float64), deadline_ms / 1e3)
            stats.head_service_seconds.append(svc)
            return jnp.asarray(logits_np), rel + 1
        release = (self.m if layer_budget is None
                   else max(1, min(layer_budget, self.m)))
        series = self.head_series(hidden)      # (m, B, V)
        return series[release - 1], release

    def decode(self, tokens, caches, start_pos: int, num_tokens: int, *,
               layer_budget: Optional[int] = None,
               deadline_ms: Optional[float] = None):
        """Greedy decode; each step releases logits at the resolution the
        budget allows.  Returns (tokens (B, num_tokens), stats).

        With ``deadline_ms``, ``stats.released_at_layer`` counts layered
        resolutions (1..2m-1: the runtime decomposes BOTH operands);
        otherwise head planes (1..m).  ``stats.resolutions`` carries the
        scale in use.
        """
        if layer_budget is not None and deadline_ms is not None:
            raise ValueError(
                "layer_budget and deadline_ms are mutually exclusive "
                "budgets; pass one or the other")
        stats = ServeStats(resolutions=(2 * self.m - 1
                                        if deadline_ms is not None
                                        else self.m))
        job, tr = self._job, self.tracer
        rec = telemetry.active_tracer(tr)
        watcher = (contextlib.nullcontext() if rec is None
                   else _ReleaseWatcher(rec, job))
        tok = tokens
        out = []
        with telemetry.span(telemetry.SERVE_DECODE, "serve.decode", tr,
                            job=job), watcher:
            for i in range(num_tokens):
                with telemetry.span(telemetry.STEP, "serve.step", tr,
                                    job=job, round=i):
                    with telemetry.span(telemetry.HIDDEN_STEP,
                                        "serve.hidden_step", tr, job=job,
                                        round=i):
                        hidden, caches, *pairs = self.hidden_step(
                            self.params, tok, caches,
                            jnp.int32(start_pos + i))
                    with telemetry.span(telemetry.HEAD, "serve.head", tr,
                                        job=job, round=i):
                        logits, release = self._head(hidden, layer_budget,
                                                     deadline_ms, stats)
                    stats.steps += 1
                    stats.full_resolution += int(
                        release == stats.resolutions)
                    stats.released_at_layer.append(release)
                    tok = jnp.argmax(logits, axis=-1).astype(
                        jnp.int32)[:, None]
                    if rec is not None:
                        watcher.put(i, release, tok, *pairs)
                out.append(tok)
        return jnp.concatenate(out, axis=1), stats


def main(argv=None) -> int:
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="llama3-8b-smoke")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--layer-budget", type=int, default=None,
                    help="resolutions computable per step (None = all)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="wall-clock budget per decode step; the head "
                         "runs as a deadline-bounded runtime job")
    ap.add_argument("--planes", type=int, default=2)
    args = ap.parse_args(argv)

    if args.arch.endswith("-smoke"):
        cfg = registry.get_smoke_config(args.arch[: -len("-smoke")])
    else:
        cfg = registry.get_config(args.arch)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    server = ProgressiveServer(cfg, params, m=args.planes)

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                      (args.batch, args.prompt_len)),
                         jnp.int32)
    max_len = args.prompt_len + args.gen
    extras = {}
    if cfg.is_encdec:
        extras["audio_embeds"] = jnp.zeros(
            (args.batch, cfg.encoder_seq, cfg.d_model), cfg.cdtype())
    if cfg.num_image_tokens:
        extras["extra_embeds"] = jnp.zeros(
            (args.batch, cfg.num_image_tokens, cfg.d_model), cfg.cdtype())
    try:
        _, caches = server.prefill(tokens, max_len, **extras)
        out, stats = server.decode(tokens[:, -1:], caches, args.prompt_len,
                                   args.gen,
                                   layer_budget=args.layer_budget,
                                   deadline_ms=args.deadline_ms)
    finally:
        server.close()
    print(f"[serve] generated {out.shape} tokens; "
          f"{stats.full_resolution}/{stats.steps} steps at full resolution "
          f"(of {stats.resolutions}); "
          f"release layers: {stats.released_at_layer}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
