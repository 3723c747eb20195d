"""Pallas TPU kernel: layered-resolution int8 matmul (the paper's mini-job
grid as ONE fused MXU pass).

TPU-native rethinking of §III (DESIGN.md §6): instead of shipping the m**2
digit-plane mini-jobs ``A_i^T B_j`` to separate workers, the kernel walks
the anti-diagonals **MSB-first inside the systolic array's dataflow**: for
each (M, N) output tile it accumulates the plane-pair products layer by
layer into an (L, bm, bn) VMEM tile, so after layer ``l``'s planes the tile
already holds a *valid Definition-1 resolution*.  A deadline-bounded server
reads resolution ``l`` from output row ``l`` — the early-release semantics
come for free from the accumulation order.

Grid: ``(M/bm, N/bn, K/bk)`` with the K axis innermost (sequential
accumulation into the output tile, standard Pallas matmul pattern).  Planes
are int8 (use digit width d <= 7 so unsigned digits fit int8); per-plane
products run on the MXU via ``preferred_element_type=int32`` and are scaled
into the fp32 accumulator by ``2**((i+j) d)``.

VMEM per step (defaults bm=bn=128, bk=512, m=2):
  A tile  m*bk*bm  int8 = 128 KiB       B tile  m*bk*bn int8 = 128 KiB
  out     L*bm*bn  fp32 = 192 KiB       -- comfortably inside 16 MiB VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import layering

__all__ = ["layered_matmul_kernel_call"]


def _kernel(a_ref, b_ref, out_ref, *, m: int, d: int, nk: int):
    """One (mi, ni, ki) grid step.

    a_ref: (m, bk, bm) int8    b_ref: (m, bk, bn) int8
    out_ref: (L, bm, bn) int32, accumulated across ki.

    Emits EXACT per-layer partial sums ``sum_{i+j = 2m-2-l} A_i^T B_j``
    (unscaled, non-cumulative): the fusion applies the ``2**((i+j) d)``
    scales and the cumulative sum (ops.py), exactly mirroring the paper's
    worker/fusion split.  int32 is exact for J(l)*K*(2^d-1)^2 < 2^31 —
    e.g. d=7, K <= 32768, J <= 4.
    """
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    L = 2 * m - 1
    for l in range(L):
        part = jnp.zeros(out_ref.shape[1:], jnp.int32)
        for (i, j) in layering.layer_minijobs(m, l):
            prod = jax.lax.dot_general(
                a_ref[i], b_ref[j],
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            part = part + prod
        out_ref[l, :, :] += part


@functools.partial(jax.jit,
                   static_argnames=("m", "d", "bm", "bn", "bk", "interpret"))
def layered_matmul_kernel_call(a_planes: jax.Array, b_planes: jax.Array, *,
                               m: int, d: int, bm: int = 128, bn: int = 128,
                               bk: int = 512,
                               interpret: bool = False) -> jax.Array:
    """Exact per-layer partial sums of ``A^T B`` from int8 digit planes.

    a_planes: (m, K, M) int8   b_planes: (m, K, N) int8
    Returns (L, M, N) int32; row ``l`` holds the UNSCALED layer-l partial
    ``sum_{i+j = 2m-2-l} A_i^T B_j`` — the fusion step (ops.layered_matmul)
    applies ``2**((i+j) d)`` and the cumulative sum.
    """
    mm, K, M = a_planes.shape
    _, _, N = b_planes.shape
    if mm != m or b_planes.shape[0] != m:
        raise ValueError(f"plane count mismatch: {a_planes.shape} vs m={m}")
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    if M % bm or N % bn or K % bk:
        raise ValueError(f"dims ({M},{N},{K}) not divisible by blocks "
                         f"({bm},{bn},{bk})")
    L = 2 * m - 1
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_kernel, m=m, d=d, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, bk, bm), lambda mi, ni, ki: (0, ki, mi)),
            pl.BlockSpec((m, bk, bn), lambda mi, ni, ki: (0, ki, ni)),
        ],
        out_specs=pl.BlockSpec((L, bm, bn), lambda mi, ni, ki: (0, mi, ni)),
        out_shape=jax.ShapeDtypeStruct((L, M, N), jnp.int32),
        # M/N output tiles are independent (megacore-parallel); the K axis
        # accumulates into the output tile and must stay sequential.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a_planes, b_planes)
