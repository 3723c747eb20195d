"""Pure-jnp/NumPy oracles for every Pallas kernel (the allclose targets)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import layering

__all__ = ["layered_matmul_ref", "flash_attention_ref"]


def layered_matmul_ref(a_planes, b_planes, *, d: int) -> np.ndarray:
    """(m, K, M) x (m, K, N) int planes -> (L, M, N) float64 resolutions.

    Host NumPy, exact: the same Definition-1 cumulative anti-diagonal sums
    the kernel accumulates.
    """
    return layering.layered_planes_reference(
        np.asarray(a_planes, dtype=np.int64),
        np.asarray(b_planes, dtype=np.int64), d=d).astype(np.float64)


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        window: int | None = None) -> jax.Array:
    """Naive softmax attention over (BH, S, dh); fp32 math, q.dtype out."""
    q32 = jnp.asarray(q, jnp.float32)
    k32 = jnp.asarray(k, jnp.float32)
    v32 = jnp.asarray(v, jnp.float32)
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqd,bkd->bqk", q32, k32) * scale
    Sq, Skv = s.shape[-2], s.shape[-1]
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Skv)[None, :]
    ok = jnp.ones((Sq, Skv), bool)
    if causal:
        ok = ok & (kpos <= qpos)
    if window is not None:
        ok = ok & (kpos > qpos - window)
    s = jnp.where(ok[None], s, -0.7 * np.finfo(np.float32).max)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v32).astype(q.dtype)
