"""Pallas TPU kernel: QSGD quantize + bit-pack.

Grid over bucket rows; each step quantizes TB buckets of Bq entries.
VMEM per step: x tile + rand tile + packed tile ≈ TB*Bq*9 bytes — tiled to
stay ≤ ~1 MB. The pack step shifts and ORs vpw = 32/bits lane slices of
width W = Bq*bits/32 into one (TB, W) word tile: pure VPU work, no
gathers (layout in qsgd_pack/ref.py).

Stochastic-rounding noise arrives as an explicit uint32 operand (portable,
reproducible, interpret-testable). On real TPU this can be swapped for
pltpu.prng_random_bits seeded per grid step — flagged, not default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.qsgd_pack.ref import levels, unit_uniform
from repro.kernels.tiling import row_tile


def _kernel(x_ref, rand_ref, packed_ref, scale_ref, *, bits: int, scale_mode: str):
    x = x_ref[...].astype(jnp.float32)  # (TB, Bq)
    bq = x.shape[1]
    vpw = 32 // bits
    s = levels(bits)
    if scale_mode == "l2":
        scale = jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True))
    else:
        scale = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    safe = jnp.where(scale > 0, scale, 1.0)
    u = unit_uniform(rand_ref[...])
    level = jnp.floor(jnp.abs(x) / safe * s + u)
    level = jnp.clip(level, 0, s).astype(jnp.int32)
    code = jnp.where(x < 0, -level, level) + s
    code = jnp.where(scale > 0, code, s)
    # Planar layout: word i holds the codes of entries i, W+i, 2W+i, ...
    # so each field is a whole-lane slice, shifted and OR-ed in: no
    # lane-splitting reshape, which the TPU kernel compiler refuses. The
    # fields are disjoint, so int32 arithmetic gives the u32 word's bits.
    w = bq // vpw
    packed = code[:, :w]
    for j in range(1, vpw):
        packed = packed | (code[:, j * w:(j + 1) * w] << (j * bits))
    packed_ref[...] = jax.lax.bitcast_convert_type(packed, jnp.uint32)
    scale_ref[...] = scale


def qsgd_pack_pallas(
    x: jax.Array,
    rand: jax.Array,
    bits: int,
    scale_mode: str = "l2",
    *,
    interpret: bool = True,
    tb: int | None = None,
):
    nb, bq = x.shape
    vpw = 32 // bits
    w = bq // vpw
    if tb is None:
        tb = row_tile(nb, 65536 // bq)
    return pl.pallas_call(
        functools.partial(_kernel, bits=bits, scale_mode=scale_mode),
        grid=(pl.cdiv(nb, tb),),
        in_specs=[
            pl.BlockSpec((tb, bq), lambda i: (i, 0)),
            pl.BlockSpec((tb, bq), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tb, w), lambda i: (i, 0)),
            pl.BlockSpec((tb, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, w), jnp.uint32),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, rand)
