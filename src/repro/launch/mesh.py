"""Mesh construction.

Functions, not module-level constants, so importing this module never
touches jax device state (the dry-run sets
``--xla_force_host_platform_device_count`` *before* first jax init).

Every mesh in the repo is built by :func:`make_mesh`, which gives each
axis the ``Auto`` type.  Newer JAX defaults ``jax.make_mesh`` to
``Explicit`` axes, under which sharded arrays carry their sharding in
their type and a plain gather such as ``embed[tokens]`` on a sharded
table is refused; the programs here leave layout to the partitioner
(``shard_map`` steps, GSPMD serving shardings).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with ``Auto`` axis types on ``devices`` (default:
    all local devices)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
