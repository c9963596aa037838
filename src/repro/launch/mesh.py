"""Every mesh this program builds.  FUNCTIONS (not module-level constants)
so importing this module never touches jax device state — only dryrun.py
forces the 512-device host platform."""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> jax.sharding.Mesh:
    """Mesh over the local devices with every axis ``Auto``.  jax.make_mesh
    defaults to Explicit axes, under which ops such as the embedding gather
    must be told their output sharding; the sharding rules here leave that
    to XLA's propagation."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Single pod: 256 chips as (16, 16) ("data", "model").
    Multi-pod: 2 pods = 512 chips as (2, 16, 16) ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
