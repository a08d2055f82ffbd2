"""Production meshes.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state). Single-pod: (16, 16) = ("data", "model") — one v5e pod of
256 chips. Multi-pod: (2, 16, 16) = ("pod", "data", "model") — 512 chips;
the "pod" axis only ever carries batch (pure DP across pods: the slowest
links are crossed by exactly one gradient all-reduce per step).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType

from repro.config.base import MeshConfig

SINGLE_POD = MeshConfig(shape=(16, 16), axes=("data", "model"))
MULTI_POD = MeshConfig(shape=(2, 16, 16), axes=("pod", "data", "model"))


def _auto_mesh(shape, axes) -> jax.sharding.Mesh:
    # Auto axes: the models place activations with with_sharding_constraint
    # (launch/sharding.shard), which rejects make_mesh's default Explicit axes.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(config: MeshConfig) -> jax.sharding.Mesh:
    return _auto_mesh(config.shape, config.axes)


def make_host_mesh(model_axis: Optional[int] = None) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    model = model_axis or 1
    return _auto_mesh((n // model, model), ("data", "model"))
