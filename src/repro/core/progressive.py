"""Progressive-precision (layered) linear layers for deadline-bounded serving.

The paper's layered resolution (§III; docs/paper-map.md), applied on-chip:
weights (and optionally activations) are digit-decomposed; computing digit
planes MSB-first means a valid approximate output exists after every
plane — a server hitting its deadline releases the best available
resolution instead of nothing.

Two modes:

* ``weight-only`` (production): only W is decomposed into ``m`` planes;
  activations stay float.  Resolution l uses planes ``m-1 .. m-1-l``:
  ``y_l = x @ (sum_{i >= m-1-l} W_i 2^{id}) * scale`` — m resolutions.
* ``two-sided`` (paper-faithful): both x and W are quantized and decomposed;
  mini-jobs follow Definition 1's anti-diagonals — ``2m-1`` resolutions.

``launch/serve.py`` serves an LM's final projection in the weight-only mode:
the serving hot-spot where vocab-size matmuls dominate decode latency.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import layering

__all__ = [
    "LayeredLinear", "make_layered_linear", "layered_linear_apply",
    "two_sided_layered_matmul", "resolution_series", "plane_step",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LayeredLinear:
    """Digit-plane decomposed weight matrix.

    planes: (m, d_in, d_out) int8 digit planes (LSB at index 0; the top
            plane is signed, lower planes are unsigned d-bit digits stored
            in int8 -- valid for d <= 7, or d = 8 stored offset-free in
            int16 planes).
    scale:  float32 scalar; W ~= reconstruct(planes) * scale.
    d:      digit width in bits.
    """

    planes: jax.Array
    scale: jax.Array
    d: int = dataclasses.field(metadata=dict(static=True))

    @property
    def m(self) -> int:
        return self.planes.shape[0]

    @property
    def num_resolutions(self) -> int:
        return self.m


def make_layered_linear(w: jax.Array, *, m: int, d: int) -> LayeredLinear:
    """Quantize float weights (d_in, d_out) to m*d bits and decompose."""
    q, scale = layering.quantize(w, m * d)
    planes = layering.decompose(q, m, d)
    dtype = jnp.int8 if d <= 7 else jnp.int16
    return LayeredLinear(planes=planes.astype(dtype), scale=scale, d=d)


@functools.partial(jax.jit, static_argnames=("resolution",))
def layered_linear_apply(params: LayeredLinear, x: jax.Array,
                         resolution: Optional[int] = None) -> jax.Array:
    """``x @ W`` truncated to the given resolution (None = full).

    MSB-first partial sums: resolution l uses the top l+1 planes.  Uses one
    fused matmul over the selected planes (the Pallas kernel path computes
    the same contraction plane-by-plane with early exit; see
    ``repro.kernels``).
    """
    m = params.m
    l = m - 1 if resolution is None else resolution
    if not 0 <= l < m:
        raise ValueError(f"resolution {l} out of range (m={m})")
    top = [params.planes[i].astype(x.dtype) * float(1 << (i * params.d))
           for i in range(m - 1 - l, m)]
    w_eff = sum(top) * params.scale.astype(x.dtype)
    return x @ w_eff


def plane_step(params: LayeredLinear, x: jax.Array, l: int,
               acc: Optional[jax.Array] = None) -> jax.Array:
    """One MSB-first incremental step: add plane ``m-1-l``'s contribution.

    Returns the UNSCALED accumulator (multiply by ``params.scale`` for the
    resolution-``l`` output).  The single source of the per-plane math —
    :func:`resolution_series` and the deadline-bounded server
    (``repro.launch.serve``) both build on it.
    """
    i = params.m - 1 - l
    contrib = (x @ params.planes[i].astype(x.dtype)) * float(1 << (i * params.d))
    return contrib if acc is None else acc + contrib


def resolution_series(params: LayeredLinear, x: jax.Array) -> jax.Array:
    """All m weight-only resolutions, shape (m, *x.shape[:-1], d_out).

    Computed incrementally (one plane matmul per step), mirroring what a
    deadline-bounded server does; ``series[-1]`` equals the full-precision
    quantized product.
    """
    outs = []
    acc = None
    for l in range(params.m):
        acc = plane_step(params, x, l, acc)
        outs.append(acc * params.scale.astype(x.dtype))
    return jnp.stack(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("m", "d"))
def two_sided_layered_matmul(x: jax.Array, w: jax.Array, *, m: int, d: int):
    """Paper-faithful two-sided layering of ``x @ w``; returns (L, ..., out).

    Both operands are quantized to ``m*d`` bits, digit-decomposed, and the
    m**2 mini-jobs are accumulated along Definition-1 anti-diagonals.
    Output resolutions are float32, rescaled to the original value range.
    """
    qx, sx = layering.quantize(x, m * d)
    qw, sw = layering.quantize(w, m * d)
    cx = layering.decompose(qx, m, d).astype(jnp.float32)
    cw = layering.decompose(qw, m, d).astype(jnp.float32)
    L = layering.num_layers(m)
    outs, acc = [], None
    for l in range(L):
        part = None
        for (i, j) in layering.layer_minijobs(m, l):
            prod = cx[i] @ cw[j] * float(1 << ((i + j) * d))
            part = prod if part is None else part + prod
        acc = part if acc is None else acc + part
        outs.append(acc)
    scale = (sx * sw).astype(jnp.float32)
    return jnp.stack(outs, axis=0) * scale

