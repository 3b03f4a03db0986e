"""Device meshes for the launchers and the sharded tests.

Functions, not module-level constants, so importing never touches jax
device state (the dry-run must set XLA_FLAGS before first jax init).

Every mesh here has Auto axis types: the logical-axis rules
(parallel/sharding.py) place parameters and batches, and XLA's
partitioner propagates the rest. ``jax.make_mesh`` alone gives Explicit
axes, under which a dot whose contracting dims are sharded (FSDP on the
embed dim) raises instead of being partitioned. Enter a mesh with
``jax.set_mesh(mesh)``, which replaces ``with mesh:``.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """A mesh of ``shape`` over ``axes`` with Auto axis types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod (v5e pod slice); 2 pods = 512 chips."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))
