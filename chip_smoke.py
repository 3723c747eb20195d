"""Bring-up check: the system's main path on a TPU, at published widths.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: the runtime phase only

Phases, in order, each printing one line:

  device   JAX's devices; fails unless the first one is a TPU.
  kernel   the layered-matmul Pallas kernel (``ops.layered_matmul_partials``)
           at yi-6b's LM-head width, compiled for the chip; its int32
           partials must equal the host oracle exactly.
  serve    ``ProgressiveServer`` on yi-6b at published widths, depth cut to
           8 of 32 layers, random weights from a seed, in both budget
           modes: the on-chip layered head (``layer_budget=None``) and the
           deadline mode, whose head runs on the host thread fleet.
  runtime  ``run_jobs`` on the ``jax`` backend with LM-head-sized operands,
           each decode checked against the exact layered oracle.

Every check is an assertion with its tolerance written next to it; any
failure ends the script with a non-zero code before the last line.  The
last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Times printed along the way are informational: compile times show whether
the persistent compilation cache was warm.
"""

from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import pathlib
import resource
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import registry  # noqa: E402
from repro.core import layering  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch.serve import ProgressiveServer  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.runtime import RuntimeConfig, run_jobs  # noqa: E402

SEED = 0
#: float32 unit roundoff
U32 = 2.0 ** -24

# yi-6b: 32 layers, d_model 4096, 32 heads / 4 KV heads of 128, d_ff 11008,
# vocab 64000 (configs/yi_6b.py).  Depth is cut to 8 layers so that the
# fp32 master weights (7.1 GiB) and the int8 head planes fit one 16 GB chip
# beside their transients; every width is the published one.
SERVE_ARCH = "yi-6b"
SERVE_LAYERS = 8
SERVE_BATCH, SERVE_PROMPT, SERVE_GEN = 4, 128, 8
HEAD_M, HEAD_D = 2, 7
SERVE_DEADLINE_MS = 100.0

# the LM head as a layered matmul: a = hidden.T (K, M), b = W (K, N)
HEAD_K, HEAD_M_ROWS, HEAD_N = 4096, 8, 64000

# Coded runtime geometry.  n2 = 8 splits the 64000-wide operand into 8000
# columns per task.  n1 = 1 keeps the recovery threshold at k = n1 * n2 = 8:
# the decode is a Vandermonde solve over k Chebyshev points, whose condition
# number grows about 2.4x per extra point, so float32 task products decode
# to ~1e-4 at k = 8 but to garbage (relative error > 1) at k = 16.
RUNTIME_N1, RUNTIME_N2, RUNTIME_JOBS = 1, 8, 10


def peak_rss_gib() -> float:
    """This process's peak resident set so far (Linux reports KiB)."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


def device_phase(*, chips: int) -> dict:
    devices = jax.devices()
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    print(f"[device] platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX's first device is "
                         f"{dev.platform!r}, not a TPU; no fallback")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but JAX sees "
                         f"{len(devices)} device(s)")
    return info


def kernel_phase(*, K: int, M: int, N: int, m: int, d: int,
                 interpret: bool = False) -> None:
    """Compile the Pallas kernel and check it exactly against the oracle."""
    rng = np.random.default_rng(SEED)
    hi = 1 << (m * d - 1)
    a = rng.integers(-hi, hi, size=(K, M), dtype=np.int32)
    b = rng.integers(-hi, hi, size=(K, N), dtype=np.int32)
    a_dev, b_dev = jnp.asarray(a), jnp.asarray(b)

    t0 = time.perf_counter()
    compiled = ops.layered_matmul_partials.lower(
        a_dev, b_dev, m=m, d=d, interpret=interpret).compile()
    compile_s = time.perf_counter() - t0
    if not interpret:
        # compiled for the chip, the Pallas kernel is a Mosaic custom call;
        # without it the product ran as plain XLA ops
        assert "tpu_custom_call" in compiled.as_text(), (
            "no tpu_custom_call in the compiled kernel program")
    t0 = time.perf_counter()
    parts = compiled(a_dev, b_dev).block_until_ready()
    run_s = time.perf_counter() - t0
    parts = np.asarray(parts).astype(np.int64)

    # tolerance 0: int8 planes, int32 accumulation, exact while
    # J(l) * K * (2^d - 1)^2 < 2^31 (2 * 4096 * 127^2 = 1.3e8 here); the
    # host fusion below is int64 and the oracle's float64 sums stay < 2^53
    assert K * 2 * (2 ** d - 1) ** 2 < 2 ** 31, "int32 partials could wrap"
    scales = np.asarray([1 << ((2 * m - 2 - l) * d)
                         for l in range(2 * m - 1)], np.int64)
    fused = np.cumsum(parts * scales[:, None, None], axis=0)
    pa = layering._np_decompose(a, m, d)
    pb = layering._np_decompose(b, m, d)
    want = ref.layered_matmul_ref(pa, pb, d=d)
    assert np.array_equal(fused.astype(np.float64), want), (
        f"kernel partials differ from layered_matmul_ref: max abs diff "
        f"{np.abs(fused - want).max()}")
    # and the oracle's last resolution is the exact product
    assert np.array_equal(want[-1], layering.exact_int_matmul(a, b))
    print(f"[kernel] ok K={K} M={M} N={N} m={m} d={d}: int32 partials == "
          f"layered_matmul_ref exactly"
          f"{'' if interpret else ', tpu_custom_call in program'}; "
          f"compile {compile_s:.3f} s, run {run_s * 1e3:.3f} ms "
          f"(informational)", flush=True)


def serve_phase(cfg, *, batch: int, prompt_len: int, gen: int, m: int,
                d: int, deadline_ms: float) -> None:
    """ProgressiveServer through prefill and decode, both budget modes."""
    t0 = time.perf_counter()
    # one program rather than an op-by-op dispatch per weight
    params = jax.jit(T.init_params, static_argnums=1)(
        jax.random.PRNGKey(SEED), cfg)
    jax.block_until_ready(params)
    n_params = T.count_params(params)
    init_s = time.perf_counter() - t0
    rng = np.random.default_rng(SEED)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                      (batch, prompt_len)), jnp.int32)

    with ProgressiveServer(cfg, params, m=m, d=d) as server:
        t0 = time.perf_counter()
        _, caches = server.prefill(tokens, prompt_len + gen)
        jax.block_until_ready(caches)
        prefill_s = time.perf_counter() - t0

        # -- on-chip head: the full resolution against hidden @ W --------
        last = tokens[:, -1:]
        t0 = time.perf_counter()
        hidden, _ = server.hidden_step(server.params, last, caches,
                                       jnp.int32(prompt_len))
        hidden = hidden.block_until_ready()
        step_first_s = time.perf_counter() - t0
        series = np.asarray(server.head_series(hidden), np.float64)
        h = jnp.asarray(hidden, jnp.float32)
        w = jnp.asarray(params["embed"].T if cfg.tie_embeddings
                        else params["lm_head"], jnp.float32)
        hi = jax.lax.Precision.HIGHEST
        exact = np.asarray(jnp.matmul(h, w, precision=hi), np.float64)
        abs_hw = np.asarray(jnp.matmul(jnp.abs(h), jnp.abs(w), precision=hi),
                            np.float64)
        h1 = np.asarray(jnp.abs(h).sum(axis=-1), np.float64)[:, None]
        scale = float(server.lm_head.scale)
        # tolerance: layering.quantize rounds W to m*d = 14-bit steps of
        # ``scale``, so |x @ (W_q - W)| <= ||x||_1 * scale / 2; both float32
        # matmuls (head and reference) add at most K * 2^-24 * (|x| @ |W|)
        K = h.shape[-1]
        bound = h1 * scale / 2 + 2 * K * U32 * abs_hw
        err = np.abs(series[-1] - exact)
        assert series.shape == (m, batch, cfg.vocab_size), series.shape
        assert np.isfinite(series).all(), "non-finite logits"
        assert (err <= bound).all(), (
            f"full-resolution logits off by {err.max():.3e} > bound "
            f"{bound[err > bound].min():.3e}")

        t0 = time.perf_counter()
        out, stats = server.decode(last, caches, prompt_len, gen)
        out = np.asarray(out)
        decode_s = time.perf_counter() - t0
        assert out.shape == (batch, gen), out.shape
        assert ((0 <= out) & (out < cfg.vocab_size)).all(), "bad token ids"
        assert stats.full_resolution == gen, stats.released_at_layer
        print(f"[serve] ok on-chip head: {cfg.name} {cfg.num_layers}/"
              f"{registry.get_config(cfg.name).num_layers} layers, "
              f"{n_params / 1e9:.3f} B params, batch {batch}, prompt "
              f"{prompt_len}, {gen} tokens; full-resolution logits within "
              f"the quantisation bound (max err {err.max():.3e}, "
              f"max err/bound {(err / bound).max():.3e}); serving copy "
              f"in {cfg.compute_dtype} {server.compute_copy_bytes} "
              f"bytes; init "
              f"{init_s:.2f} s, prefill {prefill_s:.2f} s, first "
              f"hidden_step {step_first_s:.2f} s, decode {decode_s:.2f} s "
              f"(informational)", flush=True)

        # -- deadline mode: the head is a runtime job on host threads ----
        t0 = time.perf_counter()
        out, stats = server.decode(last, caches, prompt_len, gen,
                                   deadline_ms=deadline_ms)
        out = np.asarray(out)
        host_s = time.perf_counter() - t0
        # every step releases at least resolution 0 (released_at_layer
        # counts resolutions from 1): the runtime's guaranteed minimum
        assert len(stats.released_at_layer) == gen, stats.released_at_layer
        assert min(stats.released_at_layer) >= 1, stats.released_at_layer
        assert ((0 <= out) & (out < cfg.vocab_size)).all(), "bad token ids"
        svc = np.asarray(stats.head_service_seconds)
        print(f"[serve] ok host head (deadline {deadline_ms:g} ms, thread "
              f"fleet): releases per step {stats.released_at_layer} of "
              f"{stats.resolutions}, all >= res 0; head service median "
              f"{np.median(svc):.3f} s, decode {host_s:.2f} s, peak host "
              f"RSS {peak_rss_gib():.1f} GiB (informational)", flush=True)


def decode_condition(code) -> float:
    """Largest inf-norm condition number of any k-subset decode."""
    x = code.points()
    worst = 0.0
    for ids in itertools.combinations(range(len(x)), code.k):
        v = np.vander(x[list(ids)], code.k, increasing=True)
        worst = max(worst, np.linalg.cond(v, np.inf))
    return worst


def runtime_phase(*, workers: int, K: int, M: int, N: int, jobs: int,
                  platform: str) -> None:
    """The coded runtime on the ``jax`` backend, every decode verified."""
    cfg = RuntimeConfig(mu=(400.0,) * workers, n1=RUNTIME_N1,
                        n2=RUNTIME_N2, straggler="exp", backend="jax",
                        seed=SEED)
    t0 = time.perf_counter()
    result, _ = run_jobs(cfg, jobs, K=K, M=M, N=N, verify=True)
    run_s = time.perf_counter() - t0
    placed = result.transport_stats["result_devices"]
    ids = sorted(placed)
    assert all(i.startswith(f"{platform}:") for i in ids), placed
    # one worker per device: workers p and p' share a device only when
    # there are more workers than devices
    want = min(workers, len(jax.devices()))
    assert len(ids) == want, f"{len(ids)} devices computed, want {want}"
    errs = result.verify_errors
    assert errs.shape[0] == jobs and np.isfinite(errs[:, -1]).all(), (
        "a job released no final resolution")
    cond = decode_condition(cfg.code())
    # tolerance: each float32 task product carries a few units of roundoff
    # (operands rounded to float32, K-term float32 sums); the any-k decode
    # multiplies that by at most the decode's condition number
    tol = 8 * cond * U32
    worst = float(np.nanmax(errs))
    assert worst <= tol, f"verify rel error {worst:.3e} > {tol:.3e}"
    stages = ", ".join(f"{k} {v:.2f}"
                       for k, v in result.stage_seconds.items())
    print(f"[runtime] ok backend=jax, {workers} workers on "
          f"{len(ids)} device(s) {placed}: {jobs} jobs a=({K},{M}) "
          f"b=({K},{N}), n1={cfg.n1} n2={cfg.n2} T={cfg.code().num_tasks}, "
          f"exp stragglers; max verify rel error {worst:.3e} <= "
          f"{tol:.3e} (8 * cond {cond:.3e} * 2^-24); {run_s:.2f} s "
          f"(master stages, s: {stages}), peak host RSS "
          f"{peak_rss_gib():.1f} GiB (informational)", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the runtime phase, one worker per "
                         "chip on four chips")
    args = ap.parse_args(argv)

    cache = pathlib.Path(compile_cache.enable())
    entries = len(list(cache.iterdir())) if cache.is_dir() else 0
    print(f"[cache] {cache}: {entries} entries at start", flush=True)
    info = device_phase(chips=args.chips)
    if args.chips == 4:
        runtime_phase(workers=4, K=HEAD_K, M=HEAD_M_ROWS, N=HEAD_N,
                      jobs=RUNTIME_JOBS, platform="tpu")
    else:
        kernel_phase(K=HEAD_K, M=HEAD_M_ROWS, N=HEAD_N, m=HEAD_M, d=HEAD_D)
        cfg = dataclasses.replace(registry.get_config(SERVE_ARCH),
                                  num_layers=SERVE_LAYERS)
        serve_phase(cfg, batch=SERVE_BATCH, prompt_len=SERVE_PROMPT,
                    gen=SERVE_GEN, m=HEAD_M, d=HEAD_D,
                    deadline_ms=SERVE_DEADLINE_MS)
        runtime_phase(workers=3, K=HEAD_K, M=HEAD_M_ROWS, N=HEAD_N,
                      jobs=RUNTIME_JOBS, platform="tpu")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
