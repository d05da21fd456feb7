"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state; the dry-run driver sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import and then calls :func:`make_production_mesh`.

Mesh axes:
  * ``pod``   -- DCN-class axis across pods (data parallel by default;
    the pipeline module can claim it for PP stages).
  * ``data``  -- in-pod data parallelism (batch / CFD elements).
  * ``model`` -- tensor parallelism (heads / ffn / vocab / experts).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes)
    )


def make_local_mesh(model_axis: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / CPU smoke)."""
    n = len(jax.devices())
    data = n // model_axis
    devs = np.array(jax.devices()[: data * model_axis]).reshape(
        data, model_axis
    )
    return Mesh(devs, ("data", "model"))


def data_axes(mesh: Mesh) -> tuple:
    """The axes a global batch dimension shards over."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
