"""Thin wrappers over the jax distributed APIs the repo builds on.

* ``shard_map`` normalizes the replication check flag (``check_vma``)
  behind one keyword.
* ``make_mesh`` asks for Auto axis types.  A plain ``jax.make_mesh``
  mesh has Explicit axes, whose arrays carry their sharding in their
  type, so every gather on a sharded array then needs an explicit output
  sharding; the drivers' shard_map programs state their shardings in
  full and want the compiler to place everything else.

Everything in the repo goes through these two wrappers instead of the
raw APIs.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax


def shard_map(f, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with the replication (VMA) check off by default."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check
    )


def make_mesh(shape: Sequence[int], axes: Sequence[str], **kwargs: Any):
    """``jax.make_mesh`` with Auto axis types unless the caller names some."""
    kwargs.setdefault("axis_types", (jax.sharding.AxisType.Auto,) * len(axes))
    return jax.make_mesh(tuple(shape), tuple(axes), **kwargs)
