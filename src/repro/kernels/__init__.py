"""Pallas TPU kernels (compiled for the TPU; CPU tests pass interpret=True).

  layered_matmul    the paper's mini-job grid as one fused MXU pass
  flash_attention   blockwise causal attention (prefill hot-spot)
  ssd_scan          fused Mamba2 SSD chunk scan (VMEM-resident state)
ops.py holds the jit'd public wrappers; ref.py the pure-jnp oracles
(the SSD oracle is models/ssm.ssd_scan, itself tested against the naive
per-step recurrence).
"""

from repro.kernels import ops, ref  # noqa: F401
