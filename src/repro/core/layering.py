"""Layered resolution via digit decomposition (paper §III, Definition 1).

Fixed-point operands are decomposed in base ``2**d`` into ``m`` digit-plane
chunks.  For matrices ``A = sum_i A_i 2**(i d)`` and ``B = sum_j B_j 2**(j d)``

    A^T B = sum_{i,j} A_i^T B_j 2**((i+j) d)

and grouping the ``m**2`` *mini-jobs* ``(i, j)`` by anti-diagonal
``s = i + j`` (MSB-first, i.e. largest ``s`` first) yields ``L = 2m - 1``
resolution layers.  The ``l``-th resolution (Definition 1) is the partial sum
over ``(2m-2) - l <= i + j <= 2m-2``.  Upgrading resolution ``l-1 -> l`` costs
``J(l) = min(l+1, 2m-1-l)`` extra mini-jobs and ``sum_l J(l) = m**2``:
layering adds zero total compute.

Signed integers are supported exactly: the *top* chunk is an arithmetic
right-shift (so it carries the sign) while lower chunks are unsigned
``d``-bit digits.  Reconstruction is exact for any int32/int64 input that
fits in ``m * d`` bits.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "num_layers",
    "layer_minijobs",
    "minijobs_per_layer",
    "cumulative_minijobs",
    "all_minijobs_msb_first",
    "decompose",
    "reconstruct",
    "quantize",
    "dequantize",
    "exact_int_matmul",
    "layered_matmul_reference",
    "layered_planes_reference",
    "HOST_SLAB_ELEMS",
    "resolution_error_bound",
]


# ---------------------------------------------------------------------------
# Layer bookkeeping (Definition 1)
# ---------------------------------------------------------------------------

def num_layers(m: int) -> int:
    """L = 2m - 1 resolution layers for an m-chunk decomposition."""
    if m < 1:
        raise ValueError(f"m must be >= 1, got {m}")
    return 2 * m - 1


def layer_minijobs(m: int, l: int) -> list[tuple[int, int]]:
    """Mini-jobs (i, j) that layer ``l`` adds: ``i + j = (2m-2) - l``.

    Layer 0 is the single MSB*MSB product (i = j = m-1); the final layer
    ``L-1`` is the LSB*LSB product (i = j = 0).
    """
    L = num_layers(m)
    if not 0 <= l < L:
        raise ValueError(f"layer {l} out of range for m={m} (L={L})")
    s = (2 * m - 2) - l
    return [(i, s - i) for i in range(m) if 0 <= s - i < m]


def minijobs_per_layer(m: int) -> list[int]:
    """J(l) = min(l+1, 2m-1-l); J over all layers sums to m**2."""
    return [min(l + 1, 2 * m - 1 - l) for l in range(num_layers(m))]


def cumulative_minijobs(m: int) -> list[int]:
    """Number of mini-jobs needed for resolution l: sum_{i<=l} J(i)."""
    out, tot = [], 0
    for j in minijobs_per_layer(m):
        tot += j
        out.append(tot)
    return out


def all_minijobs_msb_first(m: int) -> list[tuple[int, int, int]]:
    """All (layer, i, j) triples in execution order (MSB-first)."""
    out = []
    for l in range(num_layers(m)):
        for (i, j) in layer_minijobs(m, l):
            out.append((l, i, j))
    return out


# ---------------------------------------------------------------------------
# Digit decomposition / reconstruction
# ---------------------------------------------------------------------------

def decompose(x: jax.Array, m: int, d: int) -> jax.Array:
    """Decompose integer array into m digit-plane chunks, base 2**d.

    Returns an array of shape ``(m,) + x.shape``; ``chunks[i]`` holds digit
    ``i`` (LSB at i=0).  Chunks ``0..m-2`` are unsigned d-bit digits; chunk
    ``m-1`` is the arithmetic-shift remainder and carries the sign, so

        x == sum_i chunks[i] * 2**(i*d)            (exactly)

    for any signed x representable in the accumulator dtype.
    """
    if m < 1 or d < 1:
        raise ValueError(f"need m >= 1 and d >= 1, got m={m} d={d}")
    if not jnp.issubdtype(x.dtype, jnp.integer):
        raise TypeError(f"decompose expects an integer array, got {x.dtype}")
    x = x.astype(jnp.int32) if x.dtype.itemsize <= 4 else x
    mask = (1 << d) - 1
    chunks = []
    for i in range(m):
        shifted = jnp.right_shift(x, i * d)  # arithmetic shift on signed ints
        if i == m - 1:
            chunks.append(shifted)  # top chunk keeps sign + any overflow bits
        else:
            chunks.append(jnp.bitwise_and(shifted, mask))
    return jnp.stack(chunks, axis=0)


def reconstruct(chunks: jax.Array, d: int) -> jax.Array:
    """Inverse of :func:`decompose`: ``sum_i chunks[i] * 2**(i*d)``."""
    m = chunks.shape[0]
    weights = jnp.asarray(
        [1 << (i * d) for i in range(m)], dtype=chunks.dtype
    ).reshape((m,) + (1,) * (chunks.ndim - 1))
    return jnp.sum(chunks * weights, axis=0)


# ---------------------------------------------------------------------------
# Fixed-point quantization (float <-> int) so real matrices can be layered
# ---------------------------------------------------------------------------

def quantize(x: jax.Array, total_bits: int) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor quantization of a float array to signed ints.

    Returns ``(q, scale)`` with ``x ~= q * scale`` and
    ``q in [-(2**(b-1)-1), 2**(b-1)-1]``.
    """
    absmax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    qmax = float(2 ** (total_bits - 1) - 1)
    scale = absmax / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax)
    dtype = jnp.int32 if total_bits <= 31 else jnp.int64
    return q.astype(dtype), scale.astype(jnp.float32)


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


# ---------------------------------------------------------------------------
# Reference layered matmul (the oracle every other implementation matches)
# ---------------------------------------------------------------------------

#: host passes over a vocab-wide operand (a 4096 x 64000 LM head) work in
#: slabs of this many elements, 16 MiB of int64 or float64: a whole
#: int64 or float64 copy of such an operand would be 2 GiB
HOST_SLAB_ELEMS = 1 << 21


def _np_decompose(x: np.ndarray, m: int, d: int) -> np.ndarray:
    """NumPy twin of :func:`decompose` (host integer arithmetic, exact).

    The planes keep an integer operand's own type (a shift and a mask never
    widen a value); other operands are taken as int64.
    """
    x = np.asarray(x)
    if not np.issubdtype(x.dtype, np.integer):
        x = x.astype(np.int64)
    mask = (1 << d) - 1
    # written in place: no temporaries beside the (m, *x.shape) result,
    # which matters for vocab-wide operands
    chunks = np.empty((m,) + x.shape, dtype=x.dtype)
    for i in range(m):
        np.right_shift(x, i * d, out=chunks[i])
        if i < m - 1:
            np.bitwise_and(chunks[i], mask, out=chunks[i])
    return chunks


def exact_int_matmul(x: np.ndarray, y: np.ndarray) -> np.ndarray:
    """``x.T @ y`` of integer arrays (K, M), (K, N), exactly, as int64.

    Runs in float64 BLAS when ``K * max|x| * max|y| < 2**53``: every
    product and every partial sum is then an integer float64 holds
    exactly, so the result is exact in any summation order.  Falls back
    to int64 NumPy (no BLAS; about a hundred times slower at LM-head
    widths) otherwise.
    """
    x = np.asarray(x)
    y = np.asarray(y)
    bound = x.shape[0] * _abs_max(x) * _abs_max(y)
    if bound >= 2**53:
        return x.T.astype(np.int64) @ y.astype(np.int64)
    xt = x.T.astype(np.float64)
    out = np.empty((x.shape[1], y.shape[1]), dtype=np.int64)
    # y is widened to float64 a slab of columns at a time
    cols = max(1, HOST_SLAB_ELEMS // max(y.shape[0], 1))
    for c in range(0, y.shape[1], cols):
        out[:, c:c + cols] = xt @ y[:, c:c + cols].astype(np.float64)
    return out


def _abs_max(x: np.ndarray) -> int:
    # max|x| without an |x| temporary the size of x
    return max(int(x.max(initial=0)), -int(x.min(initial=0)))


def layered_matmul_reference(a, b, *, m: int, d: int) -> np.ndarray:
    """Exact layered computation of ``a.T @ b`` for integer a (K, M), b (K, N).

    Returns ``resolutions`` of shape (L, M, N): ``resolutions[l]`` is the
    l-th resolution per Definition 1 (cumulative over anti-diagonals
    ``s >= 2m-2-l``, scaled by ``2**(s d)``).  ``resolutions[-1] == a.T @ b``
    exactly.

    Host-side NumPy (int64 results, via :func:`exact_int_matmul`) so
    exactness never depends on jax_enable_x64; this is the oracle that
    the Pallas kernel and the jnp device path are tested against.
    """
    return layered_planes_reference(_np_decompose(a, m, d),
                                    _np_decompose(b, m, d), d=d)


def layered_planes_reference(ca: np.ndarray, cb: np.ndarray, *,
                             d: int) -> np.ndarray:
    """:func:`layered_matmul_reference` of operands already split into
    integer digit planes ``ca (m, K, M)``, ``cb (m, K, N)``."""
    m, K, M = ca.shape
    # every A plane side by side: one product per B plane, so each
    # vocab-wide B plane is widened for BLAS once rather than m times;
    # prods[j][i] = ca[i].T @ cb[j]
    a_all = ca.transpose(1, 0, 2).reshape(K, m * M)
    prods = [exact_int_matmul(a_all, cb[j]).reshape(m, M, -1)
             for j in range(m)]
    partials = []
    for l in range(num_layers(m)):
        acc = np.zeros((M, cb.shape[2]), dtype=np.int64)
        for (i, j) in layer_minijobs(m, l):
            acc += prods[j][i] << ((i + j) * d)
        partials.append(acc)
    return np.cumsum(np.stack(partials, axis=0), axis=0)


@functools.partial(jax.jit, static_argnames=("m", "d"))
def layered_matmul_jnp(a: jax.Array, b: jax.Array, *, m: int, d: int):
    """Device-side layered matmul returning float32 resolutions (L, M, N).

    Per-plane products accumulate in int32 (exact for
    ``K * (2**d - 1)**2 < 2**31``, e.g. d=8 and K <= 32768); the cross-plane
    combination ``* 2**((i+j)d)`` is float32, exact for results < 2**24 per
    plane-scale and the standard device path for layered serving.
    """
    ca = decompose(a.astype(jnp.int32), m, d)
    cb = decompose(b.astype(jnp.int32), m, d)
    L = num_layers(m)
    partials = []
    for l in range(L):
        acc = jnp.zeros((a.shape[1], b.shape[1]), dtype=jnp.float32)
        for (i, j) in layer_minijobs(m, l):
            prod = jax.lax.dot(ca[i].T, cb[j],
                               preferred_element_type=jnp.int32)
            acc = acc + prod.astype(jnp.float32) * float(1 << ((i + j) * d))
        partials.append(acc)
    return jnp.cumsum(jnp.stack(partials, axis=0), axis=0)


def resolution_error_bound(m: int, d: int, K: int, l: int) -> int:
    """Worst-case |A^T B - (A^T B)|_l| for unsigned d-bit digits.

    The missing mini-jobs are all (i, j) with i+j < (2m-2)-l; each missing
    term is bounded by K * (2**d - 1)**2 * 2**((i+j) d).
    """
    bound = 0
    for s in range(0, (2 * m - 2) - l):
        count = min(s + 1, 2 * m - 1 - s)
        bound += count * K * (2**d - 1) ** 2 * (1 << (s * d))
    return bound
