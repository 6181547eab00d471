"""Data-axis collectives with a psum-emulated fallback (DESIGN.md §4).

Inside the training step the data-parallel axes are MANUAL (shard_map)
while 'model' stays AUTO so XLA keeps inserting the tensor-parallel
collectives. An older XLA-CPU partitioner hard-aborted on every explicit
collective except ``psum`` lowered in such a partial-manual region. The
:class:`CollectiveContext` therefore carries a ``native`` switch:

* native=True  — ``jax.lax`` collectives (the train step's lowering);
* native=False — the same semantics built from ONE psum each: the rank
  writes its contribution into a zero buffer at its slot and the psum
  concatenates. Wire volume is that of a dense allreduce — correctness
  scaffolding that tests force (``lowering="emulated"``), not a fast
  path.

The emulated path cannot use ``jax.lax.axis_index`` (PartitionId is also
unsupported there), so the rank arrives as DATA: a (1,) int32 slice of a
``jnp.arange(p)`` sharded over the axis (see train_step's rank feed).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp


def _f32_safe(x: jax.Array) -> tuple[jax.Array, object]:
    """16-bit operands round-trip psum through f32 (XLA-CPU partial-manual
    bug with sub-32-bit reductions — same workaround as safe_psum)."""
    if x.dtype in (jnp.bfloat16, jnp.float16):
        return x.astype(jnp.float32), x.dtype
    return x, None


@dataclass(frozen=True)
class CollectiveContext:
    """How to talk over one mesh axis. ``rank`` is required (as a traced
    scalar) when native=False."""

    axis_name: str
    p: int
    native: bool = True
    rank: Optional[jax.Array] = None

    def axis_rank(self) -> jax.Array:
        if self.native:
            return jax.lax.axis_index(self.axis_name)
        assert self.rank is not None, "emulated collectives need a rank feed"
        return self.rank

    # -- sum ---------------------------------------------------------------
    def psum(self, x: jax.Array) -> jax.Array:
        xs, orig = _f32_safe(x)
        out = jax.lax.psum(xs, self.axis_name)
        return out.astype(orig) if orig is not None else out

    # -- all_gather (tiled, along `axis`) ----------------------------------
    def all_gather(self, x: jax.Array, *, axis: int) -> jax.Array:
        if self.native:
            return jax.lax.all_gather(x, self.axis_name, axis=axis, tiled=True)
        w = x.shape[axis]
        shape = list(x.shape)
        shape[axis] = w * self.p
        xs, orig = _f32_safe(x)
        buf = jnp.zeros(shape, xs.dtype)
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, xs, self.axis_rank() * w, axis=axis)
        out = jax.lax.psum(buf, self.axis_name)
        return out.astype(orig) if orig is not None else out

    # -- all_to_all (tiled, split+concat along `axis`) ---------------------
    def all_to_all(self, x: jax.Array, *, axis: int) -> jax.Array:
        assert x.shape[axis] % self.p == 0, (x.shape, axis, self.p)
        if self.native:
            return jax.lax.all_to_all(
                x, self.axis_name, split_axis=axis, concat_axis=axis,
                tiled=True)
        chunk = x.shape[axis] // self.p
        rank = self.axis_rank()
        xs, orig = _f32_safe(x)
        buf = jnp.zeros((self.p,) + xs.shape, xs.dtype)
        buf = jax.lax.dynamic_update_slice(
            buf, xs[None], (rank,) + (0,) * x.ndim)
        allx = jax.lax.psum(buf, self.axis_name)          # (p, *x.shape)
        mine = jax.lax.dynamic_slice_in_dim(
            allx, rank * chunk, chunk, axis=axis + 1)     # (p, ..., chunk, ..)
        out = jnp.moveaxis(mine, 0, axis).reshape(x.shape)
        return out.astype(orig) if orig is not None else out
