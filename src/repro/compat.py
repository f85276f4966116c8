"""Thin helpers over JAX API defaults the codebase relies on."""
from __future__ import annotations

from typing import Sequence

import jax


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    ``jax.make_mesh`` defaults to Explicit axes; the sharding rules,
    ``shard_map`` paths and GSPMD-partitioned steps here are written for
    Auto (sharding constraints, not sharding-in-types).
    """
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(tuple(axis_names)),
        **kw)
