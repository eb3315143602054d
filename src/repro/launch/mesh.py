"""Production mesh construction.

``make_production_mesh`` is a function (never module-level state) so that
importing this module does not touch jax device state. The single-pod mesh is
16x16 = 256 chips ("data" x "model"); the multi-pod mesh adds a leading
"pod" axis: 2 x 16 x 16 = 512 chips.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

from repro.distributed.sharding import MeshSpec


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def production_meshspec(*, multi_pod: bool = False) -> MeshSpec:
    mesh = make_production_mesh(multi_pod=multi_pod)
    data_axes = ("pod", "data") if multi_pod else ("data",)
    return MeshSpec(mesh=mesh, data_axes=data_axes)


def make_meshspec(dp: int, tp: int, devices=None) -> MeshSpec:
    """Small explicit mesh for CPU runs / tests / ODMR demos."""
    devices = devices if devices is not None else jax.devices()
    n = dp * tp
    assert len(devices) >= n, f"need {n} devices, have {len(devices)}"
    arr = np.asarray(devices[:n]).reshape(dp, tp)
    mesh = Mesh(arr, ("data", "model"))
    return MeshSpec(mesh=mesh, data_axes=("data",))
