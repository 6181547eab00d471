"""Pallas TPU kernel: densify bucketed sparse streams WITHOUT scatter.

TPU adaptation (DESIGN.md §2.1): serialized scatter-add is the natural
CPU/GPU implementation but is slow on TPU. Because SparCML streams are
bucket-uniform (k entries per B-wide bucket), densification is a one-hot
contraction:   dense[r, :] = Σ_j val[r, j] * (iota == lidx[r, j])
i.e. a (1,k)x(k,B) matmul per row — MXU/VPU work, no data-dependent stores.

VMEM per grid step: onehot (TB, k, B) f32 dominates; TB is tiled so
TB*k*B*4 ≤ ~2 MB.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import row_tile


def _kernel(lidx_ref, val_ref, out_ref):
    lidx = lidx_ref[...]  # (TB, k)
    val = val_ref[...].astype(jnp.float32)  # (TB, k)
    tb, k = lidx.shape
    b = out_ref.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (tb, k, b), 2)
    onehot = (iota == lidx[:, :, None]).astype(jnp.float32)  # OOB never matches
    out_ref[...] = jnp.sum(val[:, :, None] * onehot, axis=1).astype(out_ref.dtype)


def bucket_scatter_pallas(
    lidx: jax.Array,
    val: jax.Array,
    b: int,
    *,
    interpret: bool = True,
    tb: int | None = None,
):
    nb, k = lidx.shape
    if tb is None:
        tb = row_tile(nb, (2 * 1024 * 1024 // 4) // max(1, k * b))
    return pl.pallas_call(
        _kernel,
        grid=(pl.cdiv(nb, tb),),
        in_specs=[
            pl.BlockSpec((tb, k), lambda i: (i, 0)),
            pl.BlockSpec((tb, k), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tb, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, b), val.dtype),
        interpret=interpret,
    )(lidx, val)
