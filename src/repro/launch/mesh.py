"""Production mesh construction.

Defined as a FUNCTION (not a module-level constant) so importing this module
never touches jax device state; the dry-run sets
--xla_force_host_platform_device_count=512 before any jax import.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips ("data","model"); multi-pod: 2 pods of
    256 = 512 chips ("pod","data","model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh_for(n_devices: int, model_parallel: int = 1):
    """Elastic helper: best-effort (data, model) mesh over whatever devices
    are currently alive (used by the fault-recovery path)."""
    assert n_devices % model_parallel == 0
    return jax.make_mesh((n_devices // model_parallel, model_parallel),
                         ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))
