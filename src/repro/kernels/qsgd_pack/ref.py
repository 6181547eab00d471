"""Pure-jnp oracle for QSGD bucketed stochastic quantization + packing.

QSGD (paper §6, [Alistarh et al. 2017]): split into buckets of Bq entries,
one full-precision scale per bucket, each entry stochastically rounded to
s = 2^(bits-1) - 1 signed levels and bit-packed (32//bits codes per u32).

Shared semantics:
  x:    (nb, Bq) float
  rand: (nb, Bq) uint32 — stochastic-rounding noise (explicit operand so the
        kernel is deterministic + testable; see DESIGN.md §5.3)
  -> packed (nb, Bq*bits//32) uint32, scale (nb, 1) float32

Code for entry v with scale σ:  level = floor(|v|/σ * s + u), u∈[0,1);
stored biased: code = sign(v)*level + s ∈ [0, 2s]. σ is the bucket L2 norm
(QSGD) or max-norm (scale_mode='max').

Packing is planar: with W = Bq*bits//32 words per bucket, bits
[j*bits, (j+1)*bits) of word i hold the code of entry j*W + i.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def unit_uniform(rand: jax.Array) -> jax.Array:
    """u32 noise -> f32 uniform on [0, 1): the top 24 bits, which f32
    holds exactly. The route through int32 is the kernel's: the TPU
    kernel compiler has no u32 -> f32 conversion."""
    top = jax.lax.bitcast_convert_type(rand >> 8, jnp.int32)
    return top.astype(jnp.float32) * float(2.0**-24)


def levels(bits: int) -> int:
    return 2 ** (bits - 1) - 1


def bucket_scale(x: jax.Array, scale_mode: str) -> jax.Array:
    if scale_mode == "l2":
        return jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2, axis=1, keepdims=True))
    if scale_mode == "max":
        return jnp.max(jnp.abs(x.astype(jnp.float32)), axis=1, keepdims=True)
    raise ValueError(scale_mode)


def qsgd_pack_ref(x: jax.Array, rand: jax.Array, bits: int, scale_mode: str = "l2"):
    nb, bq = x.shape
    vpw = 32 // bits
    s = levels(bits)
    xf = x.astype(jnp.float32)
    scale = bucket_scale(xf, scale_mode)  # (nb, 1)
    safe = jnp.where(scale > 0, scale, 1.0)
    u = unit_uniform(rand)
    level = jnp.floor(jnp.abs(xf) / safe * s + u)
    level = jnp.clip(level, 0, s).astype(jnp.int32)
    code = jnp.where(xf < 0, -level, level) + s  # biased, in [0, 2s]
    code = jnp.where(scale > 0, code, s).astype(jnp.uint32)
    shifts = (jnp.arange(vpw, dtype=jnp.uint32) * bits)[None, :, None]
    packed = jnp.sum(
        code.reshape(nb, vpw, bq // vpw) << shifts, axis=1, dtype=jnp.uint32
    )
    return packed, scale
