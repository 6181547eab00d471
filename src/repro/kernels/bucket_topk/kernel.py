"""Pallas TPU kernel: per-bucket top-k selection + fused error-feedback.

One grid step processes TB buckets (rows). Working set per step:
  x tile (TB, B) + magnitude copy + one-hot accumulation -> ~3*TB*B*4 bytes
kept well under VMEM (16 MB). B is a multiple of 128 (lane width) and the
selection loop is unrolled k times (k is small: 2..64), each iteration one
row max and one row min on the VPU followed by a compare-select; a second
unrolled loop reads the k selected indices out in ascending order. There
is no serialized scatter and no sort anywhere — TPU-native by
construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import row_tile

NEG_INF = float("-inf")  # plain python float: not captured as a traced const


def _kernel(x_ref, val_ref, lidx_ref, res_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)  # (TB, B)
    tb, b = x.shape
    mag = jnp.abs(x)
    iota = jax.lax.broadcasted_iota(jnp.int32, (tb, b), 1)
    sel = jnp.zeros((tb, b), jnp.bool_)
    # Unrolled iterative argmax, written as max then lowest index at the
    # max: the same tie-break (lowest index) as jax.lax.top_k in ref.py.
    for _ in range(k):
        top = jnp.max(mag, axis=1, keepdims=True)
        j = jnp.min(jnp.where(mag == top, iota, b), axis=1, keepdims=True)
        hit = iota == j  # (TB, B) one-hot
        sel = sel | hit
        mag = jnp.where(hit, NEG_INF, mag)
    # Read the selection out in ascending index order: the i-th output is
    # the lowest selected index not yet read. No in-kernel sort (the TPU
    # kernel compiler has none); values come out by one-hot reduction.
    slot = jax.lax.broadcasted_iota(jnp.int32, (tb, k), 1)
    lidx = jnp.zeros((tb, k), jnp.int32)
    val = jnp.zeros((tb, k), jnp.float32)
    left = sel
    for i in range(k):
        j = jnp.min(jnp.where(left, iota, b), axis=1, keepdims=True)
        hit = iota == j
        v = jnp.sum(jnp.where(hit, x, 0.0), axis=1, keepdims=True)
        lidx = jnp.where(slot == i, j, lidx)
        val = jnp.where(slot == i, v, val)
        left = left & ~hit
    val_ref[...] = val.astype(val_ref.dtype)
    lidx_ref[...] = lidx
    res_ref[...] = jnp.where(sel, 0, x_ref[...])


def bucket_topk_pallas(x: jax.Array, k: int, *, interpret: bool = True, tb: int | None = None):
    """x: (nb, B) -> (val (nb,k), lidx (nb,k) i32, residual (nb,B))."""
    nb, b = x.shape
    if tb is None:
        # Target ~64K elements of x per grid step.
        tb = row_tile(nb, 65536 // b)
    grid = (pl.cdiv(nb, tb),)
    return pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=grid,
        in_specs=[pl.BlockSpec((tb, b), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((tb, k), lambda i: (i, 0)),
            pl.BlockSpec((tb, k), lambda i: (i, 0)),
            pl.BlockSpec((tb, b), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, k), x.dtype),
            jax.ShapeDtypeStruct((nb, k), jnp.int32),
            jax.ShapeDtypeStruct((nb, b), x.dtype),
        ],
        interpret=interpret,
    )(x)
