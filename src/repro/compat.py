"""The one place that adapts to the installed jax (0.9)."""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``.

    jax 0.9 defaults new meshes to Explicit axes, under which
    ``with_sharding_constraint`` rejects the repo's PartitionSpecs and
    ``shard_map`` wants a mesh context; the sharding rules here are
    written for the compiler-propagated (Auto) model.  ``devices`` picks
    the devices explicitly (e.g. a described topology's)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)

