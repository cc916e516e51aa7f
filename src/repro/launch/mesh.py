"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
initialization, and smoke tests must keep seeing 1 device.

  single pod : (data=16, model=16)               = 256 chips (one v5e pod)
  multi-pod  : (pod=2, data=16, model=16)        = 512 chips

Axis roles: ``pod`` and ``data`` carry (pure) data parallelism + FSDP
parameter sharding; ``model`` carries tensor parallelism and MoE expert
parallelism.  ``dp`` in the sharding rule table resolves to
(pod, data) on the multi-pod mesh and (data,) on the single-pod mesh.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_test_mesh", "HW"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for subprocess tests (device count forced to 8)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


# TPU v5e hardware constants for the roofline (per chip).
HW = {
    "peak_bf16_flops": 197e12,   # 197 TFLOP/s
    "hbm_bw": 819e9,             # 819 GB/s
    "ici_bw": 50e9,              # ~50 GB/s per link
    "hbm_bytes": 16 * 1024**3,   # 16 GiB
}
