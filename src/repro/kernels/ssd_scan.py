"""Pallas TPU kernel: fused Mamba2 SSD chunk scan.

The jnp SSD path (models/ssm.py) materialises per-chunk (l x l) decay and
score matrices plus per-chunk states in HBM — on the mamba2 cells the
memory term, not compute, is the post-collective bottleneck.  This kernel
keeps everything per-(batch, head) in VMEM: the running (P x N) state lives
in scratch across the chunk grid dimension, and each grid step fuses

    intra:  y_d = (C B^T  ∘  L) · (dt x)          (l x l on the MXU)
    carry:  y_o = (C · state^T) ∘ exp(acum)
    state:  state <- state * exp(acum[-1]) + ((B ∘ decay)^T · dt x)^T

for one (b, h, chunk).  Grid: (B, H, nc) with nc innermost (sequential —
the state recurrence requires it; Pallas TPU iterates the trailing grid
dim fastest, so scratch carries correctly).

VMEM at defaults (l=256, P=64, N=128, fp32): x 64 KiB + B/C 128 KiB +
L/scores 512 KiB + state 32 KiB — comfortably resident.

Single-head-group form (G == 1, the Mamba2 default at these scales): B/C
are shared across heads, indexed per (b, chunk) only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_scan_kernel_call"]


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_out_ref,
            state_ref, *, chunk: int):
    nc_idx = pl.program_id(2)

    @pl.when(nc_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0, :, 0, :].astype(jnp.float32)      # (l, P)
    dt = dt_ref[0, 0, :, 0].astype(jnp.float32)       # (l,)
    A = a_ref[0].astype(jnp.float32)                  # scalar (negative)
    Bm = b_ref[0, 0].astype(jnp.float32)              # (l, N)
    Cm = c_ref[0, 0].astype(jnp.float32)              # (l, N)

    xdt = x * dt[:, None]                             # (l, P)
    adt = A * dt                                      # (l,)
    acum = jnp.cumsum(adt)                            # (l,)

    # intra-chunk: L[i, j] = exp(acum_i - acum_j) for i >= j else 0
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    diff = acum[:, None] - acum[None, :]
    Lmat = jnp.where(li >= lj, jnp.exp(diff), 0.0)    # (l, l)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y_diag = jax.lax.dot(scores * Lmat, xdt,
                         preferred_element_type=jnp.float32)  # (l, P)

    # carried-state contribution
    state = state_ref[...]                            # (P, N)
    y_off = jax.lax.dot_general(Cm, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_off = y_off * jnp.exp(acum)[:, None]            # (l, P)

    y_ref[0, 0, :, 0, :] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: decay to chunk end, add chunk contribution
    decay = jnp.exp(acum[-1] - acum)                  # (l,)
    contrib = jax.lax.dot_general(xdt * decay[:, None], Bm,
                                  (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    state_ref[...] = state * jnp.exp(acum[-1]) + contrib      # (P, N)
    state_out_ref[0, 0, :, :] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_scan_kernel_call(x: jax.Array, dt: jax.Array, A: jax.Array,
                         Bm: jax.Array, Cm: jax.Array, *,
                         interpret: bool = False):
    """Fused SSD over chunked inputs.

    x:  (B, nc, l, H, P)   dt: (B, nc, l, H)   A: (H,)
    Bm, Cm: (B, nc, l, N)  (G = 1: shared across heads)
    Returns (y (B, nc, l, H, P) float32, final_state (B, H, P, N) float32).
    """
    Bsz, nc, l, H, P = x.shape
    N = Bm.shape[-1]
    grid = (Bsz, H, nc)
    y, state = pl.pallas_call(
        functools.partial(_kernel, chunk=l),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, l, 1, P), lambda b, h, c: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, l, 1), lambda b, h, c: (b, c, 0, h)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, 1, l, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, l, N), lambda b, h, c: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, l, 1, P), lambda b, h, c: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, nc, l, H, P), jnp.float32),
            jax.ShapeDtypeStruct((Bsz, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x, dt, A, Bm, Cm)
    return y, state
