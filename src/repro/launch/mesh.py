"""Production meshes for the multi-pod dry-run.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; everything else
sees the real single-device CPU).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.experimental import mesh_utils

__all__ = ["make_production_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh(*, tp: int = 1, data: int = 1):
    """(data, model) mesh over the devices this process actually has —
    accelerators or host-platform CPU devices alike (``jax.make_mesh``
    assumes the full accelerator complement and trips on dev boxes).

    Defaults to the degenerate 1x1 smoke-test mesh.  Axis sizes are
    validated against ``jax.device_count()``; on a CPU box, more host
    devices come from ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    (set before jax is imported), which is how CI drives the sharded
    serving parity suite."""
    if tp < 1 or data < 1:
        raise ValueError(f"make_local_mesh: bad axis sizes data={data} "
                         f"tp={tp}")
    need = data * tp
    have = jax.device_count()
    if need > have:
        raise ValueError(
            f"make_local_mesh: data={data} x model={tp} needs {need} "
            f"devices but jax sees {have} ({jax.devices()[0].platform}); "
            "on a CPU dev box set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} before "
            "importing jax")
    if need == have:
        # the whole host: order devices along the physical interconnect
        # (on a v5e 2x2, ids 0,1,3,2 form the ring; list order would
        # put a diagonal, two-hop link inside the 'model' axis)
        devices = mesh_utils.create_device_mesh((data, tp))
    else:
        devices = np.array(jax.devices()[:need]).reshape(data, tp)
    return jax.sharding.Mesh(devices, ("data", "model"))
