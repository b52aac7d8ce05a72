"""Production mesh construction (functions, not module constants, so importing this
module never touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod meshes: 16x16 = 256 chips per pod; 2 pods = 512 chips.

    The 'pod' axis is an outer pure-DP axis (cross-pod DCI); 'data'/'model' live on
    in-pod ICI. Requires xla_force_host_platform_device_count=512 on CPU (see
    the top of dryrun.py).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def mesh_tag(mesh) -> str:
    return "x".join(f"{n}{s}" for n, s in zip(mesh.axis_names, mesh.devices.shape))
