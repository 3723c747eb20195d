"""Logical-axis activation sharding constraints (MaxText-style).

Without explicit constraints, GSPMD occasionally prefers
"partial-matmul + all-reduce the activations" over "all-gather the (much
smaller) FSDP weight shards" inside scanned layers — measured on the
llama3 train cell as ~2 TB/device of fp32 batch-replicated activation
all-reduces.  Pinning activations to ``(batch, ..., tp)`` makes weight
gathering the only legal partitioning, which is the intended FSDP/TP
schedule.

Model code calls ``constrain(x, "batch", None, "tp")`` with logical names;
the mesh is ambient (context manager set by launch/steps.py around jit
tracing).  With no ambient mesh (plain tests, eager use) it's a no-op.
Specs are divisibility-checked through ``fix_spec`` with relocation
disabled, so e.g. batch=1 long-context cells silently drop the batch axis.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["constrain", "mesh_context", "current_mesh"]

_STATE = threading.local()

# logical axis -> mesh axes: batch over (pod, data); TP over model; FSDP data
_LOGICAL = {"batch": ("pod", "data"), "fsdp": ("data",), "tp": ("model",)}


def current_mesh() -> Optional[Mesh]:
    return getattr(_STATE, "mesh", None)


@contextlib.contextmanager
def mesh_context(mesh: Optional[Mesh]):
    prev = current_mesh()
    _STATE.mesh = mesh
    try:
        yield
    finally:
        _STATE.mesh = prev


def _resolve(axis, mesh: Mesh):
    if axis is None:
        return None
    names = _LOGICAL.get(axis, (axis,))
    present = tuple(a for a in names if a in mesh.axis_names)
    if not present:
        return None
    return present if len(present) > 1 else present[0]


def constrain(x: jax.Array, *logical_spec) -> jax.Array:
    """Pin ``x`` to a logical sharding if an ambient mesh is set."""
    mesh = current_mesh()
    if mesh is None:
        return x
    from repro.launch.sharding import fix_spec

    spec = tuple(_resolve(a, mesh) for a in logical_spec)
    fixed = fix_spec(x.shape, spec, mesh, relocate=False)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, fixed))
