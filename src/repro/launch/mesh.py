"""Production meshes. Functions only — importing this module never
touches jax device state (jax locks the device count on first backend
init, and only dryrun.py is allowed to set the 512-device flag)."""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_planned_mesh(cfg, shape_spec, *, multi_pod: bool = False,
                      strategy: str = "new_tpu") -> Mesh:
    """Production mesh with the paper-planned device order.

    The planner (repro.core.meshplan) permutes devices so pod-crossing
    collective endpoints are spread across host NICs; logical mesh
    coordinate i gets physical device perm[i].
    """
    from ..core.meshplan import plan_device_order, tpu_topology

    dims = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    mesh_axes = dict(zip(axes, dims))
    topo = tpu_topology(n_pods=2 if multi_pod else 1)
    result = plan_device_order(cfg, shape_spec, mesh_axes, topo, strategy)
    devices = np.asarray(jax.devices())[result.perm].reshape(dims)
    return Mesh(devices, axes)
