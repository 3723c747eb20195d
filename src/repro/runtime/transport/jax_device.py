"""The ``jax`` backend: one worker per local JAX device.

Thread workers (the in-process transport loop is identical to the
``thread`` backend — shared cancel events, zero-copy batches) whose
compute kernel lives on a JAX device: each worker pins
``jax.devices()[p % len(devices)]`` and runs its coded products as a
jitted ``device_put → matmul`` with asynchronous dispatch, synchronizing
only when the result is materialized for the fusion node.  On a
multi-device host this gives ``num_workers``-way accelerator parallelism
behind the same seam; on CPU (one device) it is a smoke-able stand-in
exercised by the conformance suite.  ``wire_stats`` (surfaced as
``RuntimeResult.transport_stats``) reports where the results were
computed: ``{"result_devices": {"<platform>:<id>": tasks}}``.

This subsumes the legacy ``RuntimeConfig.use_jax_devices`` flag:
``make_transport`` routes that flag here, so old configs keep working.
"""

from __future__ import annotations

import collections
from typing import Callable, Optional

import numpy as np

from repro.runtime.tasks import RuntimeConfig, TaskResult
from repro.runtime.transport.thread import ThreadTransport
from repro.runtime.worker import make_compute

__all__ = ["JaxDeviceTransport"]


class JaxDeviceTransport(ThreadTransport):
    """Thread transport with per-worker device-pinned JAX compute."""

    name = "jax"

    def __init__(self, cfg: RuntimeConfig,
                 sink: Callable[[TaskResult], None],
                 rng: Optional[np.random.Generator] = None,
                 tracer=None):
        import jax
        self._devices = jax.devices()
        # one counter per worker: each is written by its worker's thread
        # alone, and read only after shutdown
        self._placed = [collections.Counter()
                        for _ in range(cfg.num_workers)]
        super().__init__(cfg, sink, rng, tracer)

    def _compute_for(self, worker_id: int):
        device = self._devices[worker_id % len(self._devices)]
        return make_compute(self._cfg, worker_id, device=device,
                            placed=self._placed[worker_id])

    @property
    def wire_stats(self) -> dict:
        """Coded-task results per device that computed them."""
        total: collections.Counter = collections.Counter()
        for placed in self._placed:
            total.update(placed)
        return {"result_devices": dict(total)}
