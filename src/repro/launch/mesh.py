"""Device meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state.

Axes: ("data", "model"), with an optional leading "pod" axis.  The "pod"
axis carries data parallelism (optionally MDS-coded, see repro.core) and is
the unit of failure/erasure in the fault-tolerance design.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_test_mesh", "batch_axes"]


def _make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    # Auto axes: the model steps place activations with
    # ``with_sharding_constraint`` (launch/axes.py), which Explicit axes --
    # ``jax.make_mesh``'s default -- reject.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(data: int = 1, model: int = 1, pod: int = 0) -> Mesh:
    """Small mesh over however many (host) devices the test owns."""
    if pod:
        return _make_mesh((pod, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes that shard the global batch."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
