"""Mesh and shard_map helpers over the installed JAX.

Library code, tests and benchmarks build meshes and manual regions
through here (or via ``repro.launch.mesh``), so axis types and the
shard_map calling convention are set in one place.

Nothing in this module touches device state at import time.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` (manual axes
    are chosen per region — see :func:`shard_map`)."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False,
              axis_names: Optional[set] = None):
    """``jax.shard_map`` with ``check_vma`` off by default.

    axis_names: the MANUAL axes; every other mesh axis stays auto (XLA
    keeps inserting its collectives for them). None = manual over all axes.
    """
    kw = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)
