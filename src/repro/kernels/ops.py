"""jit'd public wrappers around the Pallas kernels.

The kernels are written for TPU VMEM and compile for the TPU by default.
A caller on another backend (the CPU tests) passes ``interpret=True``
explicitly; nothing switches to interpret mode on its own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import layering
from repro.kernels.flash_attention import flash_attention_kernel_call
from repro.kernels.layered_matmul import layered_matmul_kernel_call
from repro.kernels.ssd_scan import ssd_scan_kernel_call

__all__ = ["layered_matmul", "layered_matmul_partials", "flash_attention",
           "ssd_scan_fused"]


@functools.partial(jax.jit, static_argnames=("m", "d", "interpret"))
def layered_matmul_partials(a: jax.Array, b: jax.Array, *, m: int = 2,
                            d: int = 7,
                            interpret: bool = False) -> jax.Array:
    """Exact int32 per-layer partials of ``a.T @ b`` (the worker compute).

    Decomposes integer a (K, M), b (K, N) into int8 digit planes (d <= 7 so
    unsigned digits fit int8) and runs the fused MXU kernel.  Row ``l`` is
    the unscaled layer-l partial sum -- exact as long as
    ``J(l) * K * (2^d - 1)^2 < 2^31``.
    """
    if d > 7:
        raise ValueError("d <= 7 required for int8 digit planes")
    pa = layering.decompose(a.astype(jnp.int32), m, d).astype(jnp.int8)
    pb = layering.decompose(b.astype(jnp.int32), m, d).astype(jnp.int8)
    bm = 128 if a.shape[1] % 128 == 0 else a.shape[1]
    bn = 128 if b.shape[1] % 128 == 0 else b.shape[1]
    bk = 512 if a.shape[0] % 512 == 0 else a.shape[0]
    return layered_matmul_kernel_call(pa, pb, m=m, d=d, bm=bm, bn=bn, bk=bk,
                                      interpret=interpret)


@functools.partial(jax.jit, static_argnames=("m", "d", "interpret"))
def layered_matmul(a: jax.Array, b: jax.Array, *, m: int = 2, d: int = 7,
                   interpret: bool = False) -> jax.Array:
    """Layered Definition-1 resolutions of ``a.T @ b``.

    Kernel partials + fp32 fusion (scale by ``2**((i+j) d)`` + cumulative
    sum).  Returns (L, M, N) float32; the final row equals the exact
    product for magnitudes within fp32's 2^24 integer range -- callers
    needing bit-exact fusion use :func:`layered_matmul_partials` and fuse
    in int64/fp64 on the host.
    """
    partials = layered_matmul_partials(a, b, m=m, d=d, interpret=interpret)
    L = partials.shape[0]
    scales = jnp.asarray([float(1 << ((2 * m - 2 - l) * d))
                          for l in range(L)], jnp.float32)
    scaled = partials.astype(jnp.float32) * scales[:, None, None]
    return jnp.cumsum(scaled, axis=0)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    interpret: bool = False) -> jax.Array:
    """Flash attention for (B, S, H, dh) tensors with GQA support.

    K/V may have fewer heads (n_kv); they are broadcast group-wise without
    materialising a repeat (reshape-only) before the kernel call.
    """
    B, Sq, H, dh = q.shape
    _, Skv, n_kv, _ = k.shape
    G = H // n_kv
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, dh)
    kf = jnp.broadcast_to(k.transpose(0, 2, 1, 3)[:, :, None],
                          (B, n_kv, G, Skv, dh)).reshape(B * H, Skv, dh)
    vf = jnp.broadcast_to(v.transpose(0, 2, 1, 3)[:, :, None],
                          (B, n_kv, G, Skv, dh)).reshape(B * H, Skv, dh)
    bq = 512 if Sq % 512 == 0 else Sq
    bk = 512 if Skv % 512 == 0 else Skv
    out = flash_attention_kernel_call(qf, kf, vf, causal=causal,
                                      window=window, bq=bq, bk=bk,
                                      interpret=interpret)
    return out.reshape(B, H, Sq, dh).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_fused(x: jax.Array, dt: jax.Array, A: jax.Array,
                   Bm: jax.Array, Cm: jax.Array, *, chunk: int = 256,
                   interpret: bool = False):
    """Fused-SSD twin of ``repro.models.ssm.ssd_scan`` (G = 1 only).

    x (B, S, H, P), dt (B, S, H), A (H,), Bm/Cm (B, S, 1, N) ->
    (y (B, S, H, P) fp32, final_state (B, H, P, N) fp32).
    """
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    if S % chunk:
        raise ValueError(f"S={S} not divisible by chunk={chunk}")
    nc = S // chunk
    y, state = ssd_scan_kernel_call(
        x.reshape(B, nc, chunk, H, P), dt.reshape(B, nc, chunk, H), A,
        Bm.reshape(B, nc, chunk, N), Cm.reshape(B, nc, chunk, N),
        interpret=interpret)
    return y.reshape(B, S, H, P), state
