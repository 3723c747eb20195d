"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then calls these.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16); the "pod" axis
carries data parallelism (optionally MDS-coded, see repro.core) and is the
unit of failure/erasure in the fault-tolerance design.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

__all__ = ["make_production_mesh", "make_test_mesh", "batch_axes",
           "HardwareSpec", "TPU_V5E"]


def _make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    # Auto axes: the model steps place activations with
    # ``with_sharding_constraint`` (launch/axes.py), which Explicit axes --
    # ``jax.make_mesh``'s default -- reject.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1, pod: int = 0) -> Mesh:
    """Small mesh over however many (host) devices the test owns."""
    if pod:
        return _make_mesh((pod, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes that shard the global batch."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


class HardwareSpec:
    """Roofline constants for the target chip."""

    def __init__(self, name: str, peak_flops: float, hbm_bw: float,
                 ici_bw: float, hbm_bytes: float):
        self.name = name
        self.peak_flops = peak_flops        # bf16 FLOP/s per chip
        self.hbm_bw = hbm_bw                # bytes/s per chip
        self.ici_bw = ici_bw                # bytes/s per link
        self.hbm_bytes = hbm_bytes          # HBM capacity per chip


TPU_V5E = HardwareSpec("tpu_v5e", peak_flops=197e12, hbm_bw=819e9,
                       ici_bw=50e9, hbm_bytes=16 * 1024**3)
