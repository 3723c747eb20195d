"""Layered gradient all-reduce — the paper's resolution layers on collectives.

Beyond-paper application (DESIGN.md §3.3): gradients are quantized and
digit-decomposed (``repro.core.layering``); the all-reduce then runs
**MSB-plane-first**.  A deadline-bounded synchronous step can apply the
optimizer update from the first plane(s) and feed the unsent remainder back
as error-feedback — the paper's "release a lower resolution at the deadline"
transplanted from task results to gradient collectives.

This module provides the math (plane split / reconstruct / error feedback)
plus a ``shard_map`` execution that issues one ``psum`` per plane so the
collective schedule in the lowered HLO is visibly layered (the dry-run
counts one all-reduce per plane).  Plane psums commute with the decode
because the code is linear — summing plane-wise then reconstructing equals
reconstructing then summing, up to the shared quantization scale.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import layering

__all__ = ["plane_split", "plane_reconstruct", "layered_psum",
           "layered_allreduce_tree"]


def plane_split(g: jax.Array, m: int, d: int):
    """Quantize a float gradient tensor and split into m digit planes.

    Returns (planes (m, *g.shape) float32-encoded ints, scale).  Planes are
    float so they ride the regular all-reduce datapath; each plane's values
    fit in d bits (plus sign for the top plane), so a d<=8 plane could be
    shipped as int8 — the dtype choice is the transport's concern.
    """
    q, scale = layering.quantize(g, m * d)
    planes = layering.decompose(q, m, d).astype(jnp.float32)
    return planes, scale


def plane_reconstruct(planes: jax.Array, scale: jax.Array, d: int,
                      up_to_plane: int | None = None) -> jax.Array:
    """Rebuild the (summed) gradient from the top ``up_to_plane+1`` planes.

    ``up_to_plane`` indexes MSB-first resolutions: 0 = only the top plane.
    """
    m = planes.shape[0]
    k = m if up_to_plane is None else up_to_plane + 1
    acc = jnp.zeros(planes.shape[1:], jnp.float32)
    for i in range(m - 1, m - 1 - k, -1):
        acc = acc + planes[i] * float(1 << (i * d))
    return acc * scale


def layered_psum(planes: jax.Array, axis_name: str) -> jax.Array:
    """One psum per plane, MSB-first — the layered collective schedule.

    Inside shard_map.  Each plane is an independent all-reduce so an
    implementation with a deadline can consume the partial sums in layer
    order; XLA sees ``m`` distinct all-reduce ops (verified by the dry-run
    HLO scan).
    """
    m = planes.shape[0]
    out = []
    for i in range(m - 1, -1, -1):          # MSB plane first
        out.append(jax.lax.psum(planes[i], axis_name))
    return jnp.stack(out[::-1], axis=0)


def layered_allreduce_tree(grads, mesh: Mesh, axis: str, *, m: int = 2,
                           d: int = 8, resolution: int | None = None):
    """Data-parallel mean of a gradient pytree via layered all-reduce.

    Each leaf is quantized per-device, plane-split, psum'd plane-by-plane
    (MSB first), reconstructed at ``resolution`` (None = full), and divided
    by the axis size.  Scales are psum-maxed so all devices share one scale.
    """
    n = mesh.shape[axis]

    def per_leaf(g):
        def inner(gl):
            # shared scale: max over devices so planes are commensurable
            absmax = jax.lax.pmax(jnp.max(jnp.abs(gl)), axis)
            qmax = float(2 ** (m * d - 1) - 1)
            scale = jnp.maximum(absmax, 1e-30) / qmax
            q = jnp.clip(jnp.round(gl / scale), -qmax, qmax).astype(jnp.int32)
            planes = layering.decompose(q, m, d).astype(jnp.float32)
            planes = layered_psum(planes, axis)
            return plane_reconstruct(planes, scale, d, resolution) / n

        return jax.shard_map(inner, mesh=mesh, in_specs=P(axis),
                             out_specs=P(axis))(g)

    return jax.tree.map(per_leaf, grads)
