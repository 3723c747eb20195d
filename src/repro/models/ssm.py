"""Mamba2 (SSD — state-space duality) block, chunked scan + decode step.

Training/prefill uses the SSD chunked algorithm (Dao & Gu, arXiv:2405.21060):
the sequence is split into chunks of ``chunk_size``; each chunk computes a
dense intra-chunk (quadratic-in-chunk) term plus an inter-chunk linear
recurrence over per-chunk states — O(S) total with matmul-friendly inner
shapes (this is the TPU-appropriate formulation; the CUDA kernel's
warp-level scan does not transfer).

Decode keeps a recurrent state (B, H, P, N) plus a (d_conv-1)-deep causal
conv cache; one token costs O(H*P*N) — sequence-length-independent, which is
why mamba2 runs the ``long_500k`` cell.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SSMConfig
from repro.launch.axes import constrain
from repro.models.layers import init_linear, rms_norm

__all__ = ["init_ssm_params", "ssm_block", "ssm_decode_step", "ssd_scan",
           "init_ssm_cache"]

NGROUPS = 1  # B/C projection groups (Mamba2 default for these scales)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_ssm_params(key: jax.Array, d_model: int, cfg: SSMConfig, dtype,
                    extra_dims: tuple[int, ...] = ()) -> dict:
    """Projections are SPLIT per stream (gate/x/B/C/dt) rather than one
    fused in_proj: a fused (D, 2*d_in + 2GN + H) output sharded over the
    model axis puts every stream's slice off shard boundaries, which
    GSPMD repairs with per-layer halo collective-permutes (measured 24
    GB/device on the mamba2 prefill cell).  Separate weights make each
    stream's TP sharding exact.  Math is identical."""
    d_in = cfg.d_inner(d_model)
    H = cfg.num_heads(d_model)
    N = cfg.d_state
    ks = jax.random.split(key, 8)
    shp = lambda *s: extra_dims + s
    return {
        "gate_proj": init_linear(ks[0], d_model, d_in, dtype, extra_dims),
        "x_proj": init_linear(ks[3], d_model, d_in, dtype, extra_dims),
        "B_proj": init_linear(ks[4], d_model, NGROUPS * N, dtype,
                              extra_dims),
        "C_proj": init_linear(ks[5], d_model, NGROUPS * N, dtype,
                              extra_dims),
        "dt_proj": init_linear(ks[6], d_model, H, dtype, extra_dims),
        "conv_x": (jax.random.normal(ks[1], shp(cfg.d_conv, d_in),
                                     jnp.float32) / np.sqrt(cfg.d_conv)
                   ).astype(dtype),
        "conv_x_b": jnp.zeros(shp(d_in), dtype),
        "conv_B": (jax.random.normal(ks[7], shp(cfg.d_conv, NGROUPS * N),
                                     jnp.float32) / np.sqrt(cfg.d_conv)
                   ).astype(dtype),
        "conv_B_b": jnp.zeros(shp(NGROUPS * N), dtype),
        "conv_C": (jax.random.normal(jax.random.fold_in(key, 9),
                                     shp(cfg.d_conv, NGROUPS * N),
                                     jnp.float32) / np.sqrt(cfg.d_conv)
                   ).astype(dtype),
        "conv_C_b": jnp.zeros(shp(NGROUPS * N), dtype),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.linspace(1.0, 16.0, H, dtype=jnp.float32)),
            shp(H)).astype(jnp.float32),
        "D": jnp.ones(shp(H), jnp.float32),
        "dt_bias": jnp.broadcast_to(
            jnp.log(jnp.expm1(jnp.logspace(-3, -1, H, dtype=jnp.float32))),
            shp(H)).astype(jnp.float32),
        "norm_scale": jnp.zeros(shp(d_in), dtype),
        "out_proj": init_linear(ks[2], d_in, d_model, dtype, extra_dims),
    }


def init_ssm_cache(batch: int, d_model: int, cfg: SSMConfig, dtype) -> dict:
    """Per-stream conv caches (a single fused cache would need a concat of
    differently-sharded streams -- measured as per-layer all-to-alls)."""
    d_in = cfg.d_inner(d_model)
    H = cfg.num_heads(d_model)
    K = cfg.d_conv - 1
    N = NGROUPS * cfg.d_state
    return {
        "conv_x": jnp.zeros((batch, K, d_in), dtype),
        "conv_B": jnp.zeros((batch, K, N), dtype),
        "conv_C": jnp.zeros((batch, K, N), dtype),
        "state": jnp.zeros((batch, H, cfg.head_dim, cfg.d_state),
                           jnp.float32),
    }


def _streams(params, x, cfg: SSMConfig, d_model: int):
    """Per-stream projections: gate, xs, B, C, dt_raw."""
    dt = x.dtype
    gate = x @ params["gate_proj"].astype(dt)
    xs = x @ params["x_proj"].astype(dt)
    Bm = x @ params["B_proj"].astype(dt)
    Cm = x @ params["C_proj"].astype(dt)
    dtr = x @ params["dt_proj"].astype(dt)
    return gate, xs, Bm, Cm, dtr


# ---------------------------------------------------------------------------
# SSD chunked scan
# ---------------------------------------------------------------------------

def _segsum(a: jax.Array) -> jax.Array:
    """Lower-triangular pairwise segment sums.

    a: (..., L) -> (..., L, L) with out[..., i, j] = sum_{j < t <= i} a[t]
    (−inf above the diagonal).
    """
    L = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, chunk: int, init_state=None):
    """SSD over a full sequence.

    x:  (B, S, H, P)   per-head inputs
    dt: (B, S, H)      positive step sizes
    A:  (H,)           negative decay rates
    Bm, Cm: (B, S, G, N) input/output projections (G = NGROUPS)
    Returns (y (B, S, H, P) float32, final_state (B, H, P, N) float32).
    """
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[-2], Bm.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    rep = H // G

    xc = x.reshape(Bsz, nc, chunk, H, P).astype(jnp.float32)
    dtc = dt.reshape(Bsz, nc, chunk, H).astype(jnp.float32)
    Bc = Bm.reshape(Bsz, nc, chunk, G, N).astype(jnp.float32)
    Cc = Cm.reshape(Bsz, nc, chunk, G, N).astype(jnp.float32)

    xdt = xc * dtc[..., None]                       # dt-weighted input
    Adt = A[None, None, None, :] * dtc              # (B, nc, l, H)
    Acum = jnp.cumsum(Adt, axis=2)                  # within-chunk cumsum

    # 1) intra-chunk (quadratic in chunk length, matmul-shaped)
    Lmat = jnp.exp(_segsum(Adt.transpose(0, 1, 3, 2)))   # (B, nc, H, l, l)
    if G == 1:
        # Keep B/C in grouped (G=1) form and let the einsums sum over the
        # singleton g axis instead of jnp.repeat-ing to H: the repeat
        # produced an H-replicated (B, nc, H, l, l) score tensor that GSPMD
        # then re-sharded against the H-sharded Lmat/xdt — measured 290
        # GB/device of all-reduce/all-gather on the mamba2 prefill cell.
        scores = jnp.einsum("bclgn,bcsgn->bcls", Cc, Bc)     # tiny (g=1)
        # explicit broadcast-multiply: scores (replicated) * Lmat
        # (H-sharded) stays H-sharded; a 3-operand einsum here made GSPMD
        # all-gather Lmat to replicated (96 GB/device measured)
        W = scores[:, :, None, :, :] * Lmat                  # (B,nc,H,l,l)
        y_diag = jnp.einsum("bchls,bcshp->bclhp", W, xdt)
        decay_states = jnp.exp(Acum[:, :, -1:, :] - Acum)    # (B, nc, l, H)
        states = jnp.einsum("bclgn,bclh,bclhp->bchpn", Bc, decay_states,
                            xdt)
    else:
        Bh = jnp.repeat(Bc, rep, axis=3)   # (B, nc, l, H, N)
        Ch = jnp.repeat(Cc, rep, axis=3)
        scores_h = jnp.einsum("bclhn,bcshn->bchls", Ch, Bh)
        y_diag = jnp.einsum("bchls,bcshp->bclhp", scores_h * Lmat, xdt)
        decay_states = jnp.exp(Acum[:, :, -1:, :] - Acum)
        states = jnp.einsum("bclhn,bclh,bclhp->bchpn", Bh, decay_states,
                            xdt)

    # 3) inter-chunk recurrence (scan over chunks)
    chunk_decay = jnp.exp(Acum[:, :, -1, :])             # (B, nc, H)
    if init_state is None:
        init_state = jnp.zeros((Bsz, H, P, N), jnp.float32)

    def step(carry, inp):
        s_c, d_c = inp                                    # (B,H,P,N), (B,H)
        new = carry * d_c[:, :, None, None] + s_c
        return new, carry                                 # emit state BEFORE chunk

    final, prev_states = jax.lax.scan(
        step, init_state,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)    # (B, nc, H, P, N)

    # 4) contribution of carried state to each position
    state_decay = jnp.exp(Acum)                           # (B, nc, l, H)
    if G == 1:
        y_off = jnp.einsum("bclgn,bchpn,bclh->bclhp", Cc, prev_states,
                           state_decay)
    else:
        y_off = jnp.einsum("bclhn,bchpn,bclh->bclhp", Ch, prev_states,
                           state_decay)
    y = (y_diag + y_off).reshape(Bsz, S, H, P)
    return y, final


# ---------------------------------------------------------------------------
# Block forward (train/prefill) and decode step
# ---------------------------------------------------------------------------

def _split_proj(z: jax.Array, d_in: int, N: int, H: int):
    zs = [2 * d_in, 2 * d_in + NGROUPS * N, 2 * d_in + 2 * NGROUPS * N]
    gate_x = z[..., : 2 * d_in]
    Bm = z[..., zs[0]: zs[1]]
    Cm = z[..., zs[1]: zs[2]]
    dt = z[..., zs[2]:]
    return gate_x[..., :d_in], gate_x[..., d_in:], Bm, Cm, dt


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv over (B, S, C) with taps (K, C)."""
    K = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(pad[:, i: i + x.shape[1], :] * w[i][None, None, :]
              for i in range(K))
    return out + b[None, None, :]


def ssm_block(params: dict, x: jax.Array, d_model: int, cfg: SSMConfig,
              init_state=None):
    """Mamba2 block over (B, S, D); returns (y, cache) with final state."""
    d_in = cfg.d_inner(d_model)
    H = cfg.num_heads(d_model)
    N, P = cfg.d_state, cfg.head_dim
    gate, xs, Bm, Cm, dtr = _streams(params, x, cfg, d_model)
    gate = constrain(gate, "batch", None, "tp")
    xs = constrain(xs, "batch", None, "tp")

    K = cfg.d_conv - 1
    cache_tail = {"conv_x": xs[:, -K:], "conv_B": Bm[:, -K:],
                  "conv_C": Cm[:, -K:]}
    xs = jax.nn.silu(_causal_conv(xs, params["conv_x"].astype(x.dtype),
                                  params["conv_x_b"].astype(x.dtype)))
    Bm = jax.nn.silu(_causal_conv(Bm, params["conv_B"].astype(x.dtype),
                                  params["conv_B_b"].astype(x.dtype)))
    Cm = jax.nn.silu(_causal_conv(Cm, params["conv_C"].astype(x.dtype),
                                  params["conv_C_b"].astype(x.dtype)))

    dt = jax.nn.softplus(dtr.astype(jnp.float32)
                         + params["dt_bias"][None, None, :])
    A = -jnp.exp(params["A_log"])
    Bsz, S = x.shape[0], x.shape[1]
    xh = xs.reshape(Bsz, S, H, P)
    Bh = Bm.reshape(Bsz, S, NGROUPS, N)
    Ch = Cm.reshape(Bsz, S, NGROUPS, N)

    # Pad the sequence to a chunk multiple; padded steps have dt = 0, so
    # their decay is exp(0) = 1 and their input weight is 0 -- the final
    # state is exactly the state at position S.
    chunk = min(cfg.chunk_size, S)
    pad = (-S) % chunk
    if pad:
        padseq = lambda t: jnp.pad(t, ((0, 0), (0, pad)) +
                                   ((0, 0),) * (t.ndim - 2))
        xh, dt, Bh, Ch = map(padseq, (xh, dt, Bh, Ch))

    y, final = ssd_scan(xh, dt, A, Bh, Ch, chunk, init_state)
    y = y[:, :S] + params["D"][None, None, :, None] * xh[:, :S].astype(
        jnp.float32)
    xh = xh[:, :S]
    y = y.reshape(Bsz, S, d_in).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(gate), params["norm_scale"])
    out = constrain(y @ params["out_proj"].astype(x.dtype),
                    "batch", None, None)
    cache = dict(cache_tail, state=final)
    return out, cache


def ssm_decode_step(params: dict, x: jax.Array, cache: dict, d_model: int,
                    cfg: SSMConfig):
    """One-token Mamba2 step. x: (B, 1, D); returns (y (B,1,D), new cache)."""
    d_in = cfg.d_inner(d_model)
    H, N, P = cfg.num_heads(d_model), cfg.d_state, cfg.head_dim
    gate, xs, Bm, Cm, dtr = _streams(params, x, cfg, d_model)

    win_x = jnp.concatenate([cache["conv_x"], xs], axis=1)   # (B, K, d_in)
    win_B = jnp.concatenate([cache["conv_B"], Bm], axis=1)
    win_C = jnp.concatenate([cache["conv_C"], Cm], axis=1)
    xs = jax.nn.silu(jnp.einsum("bkc,kc->bc", win_x,
                                params["conv_x"].astype(x.dtype))
                     + params["conv_x_b"].astype(x.dtype))[:, None, :]
    Bm = jax.nn.silu(jnp.einsum("bkc,kc->bc", win_B,
                                params["conv_B"].astype(x.dtype))
                     + params["conv_B_b"].astype(x.dtype))[:, None, :]
    Cm = jax.nn.silu(jnp.einsum("bkc,kc->bc", win_C,
                                params["conv_C"].astype(x.dtype))
                     + params["conv_C_b"].astype(x.dtype))[:, None, :]

    dt = jax.nn.softplus(dtr.astype(jnp.float32)
                         + params["dt_bias"][None, None, :])[:, 0]  # (B, H)
    A = -jnp.exp(params["A_log"])
    Bsz = x.shape[0]
    xh = xs.reshape(Bsz, H, P).astype(jnp.float32)
    Bh = jnp.repeat(Bm.reshape(Bsz, NGROUPS, N), H // NGROUPS, 1)
    Ch = jnp.repeat(Cm.reshape(Bsz, NGROUPS, N), H // NGROUPS, 1)

    dA = jnp.exp(dt * A[None, :])                          # (B, H)
    dBx = jnp.einsum("bh,bhn,bhp->bhpn", dt, Bh.astype(jnp.float32),
                     xh)
    state = cache["state"] * dA[:, :, None, None] + dBx
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch.astype(jnp.float32))
    y = y + params["D"][None, :, None] * xh
    y = y.reshape(Bsz, 1, d_in).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(gate), params["norm_scale"])
    out = y @ params["out_proj"].astype(x.dtype)
    return out, {"conv_x": win_x[:, 1:], "conv_B": win_B[:, 1:],
                 "conv_C": win_C[:, 1:], "state": state}
