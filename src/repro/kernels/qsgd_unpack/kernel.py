"""Pallas TPU kernel: QSGD unpack + dequantize (lane-wise shift+mask)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.qsgd_pack.ref import levels
from repro.kernels.tiling import row_tile


def _kernel(packed_ref, scale_ref, out_ref, *, bits: int):
    # Planar layout (qsgd_pack/ref.py): field j of every word fills the
    # lane slice [j*W, (j+1)*W) of the bucket.
    packed = jax.lax.bitcast_convert_type(packed_ref[...], jnp.int32)
    w = packed.shape[1]
    vpw = 32 // bits
    s = levels(bits)
    mask = 2**bits - 1
    scale = scale_ref[...]  # (TB, 1)
    for j in range(vpw):
        # the arithmetic shift's sign bits fall outside the mask
        code = ((packed >> (j * bits)) & mask) - s
        xhat = code.astype(jnp.float32) / s * scale
        out_ref[:, j * w:(j + 1) * w] = xhat.astype(out_ref.dtype)


def qsgd_unpack_pallas(
    packed: jax.Array,
    scale: jax.Array,
    bits: int,
    out_dtype=jnp.float32,
    *,
    interpret: bool = True,
    tb: int | None = None,
):
    nb, w = packed.shape
    vpw = 32 // bits
    bq = w * vpw
    if tb is None:
        tb = row_tile(nb, 65536 // bq)
    return pl.pallas_call(
        functools.partial(_kernel, bits=bits),
        grid=(pl.cdiv(nb, tb),),
        in_specs=[
            pl.BlockSpec((tb, w), lambda i: (i, 0)),
            pl.BlockSpec((tb, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tb, bq), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, bq), out_dtype),
        interpret=interpret,
    )(packed, scale)
