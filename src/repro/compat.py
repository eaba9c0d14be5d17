"""The repo's single doorway into jax's sharding API (jax 0.9).

* :func:`shard_map` wraps ``jax.shard_map`` with the replication (VMA)
  check off by default: every call site predates it and relies on
  manual spec correctness.
* :func:`make_mesh` wraps ``jax.make_mesh`` with every axis **Auto**.
  ``jax.make_mesh`` now defaults to Explicit axes, under which a
  ``jnp.take`` on a data-sharded operand raises ``ShardingTypeError``;
  every placement in this repo is written for Auto, where the compiler
  propagates shardings.

Build meshes and shard_maps through here, not through ``jax`` directly.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "shard_map"]


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with Auto axis types (over ``devices`` if given)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check: bool = False):
    """``jax.shard_map`` with ``check_vma=check``; ``axis_names`` selects
    *partial manual* mode (manual over the named axes only)."""
    kw = {"check_vma": check}
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)
