"""Pure-jnp oracle for QSGD unpack+dequantize (inverse of qsgd_pack)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.qsgd_pack.ref import levels


def qsgd_unpack_ref(packed: jax.Array, scale: jax.Array, bits: int, out_dtype=jnp.float32):
    nb, w = packed.shape
    vpw = 32 // bits
    s = levels(bits)
    mask = jnp.uint32(2**bits - 1)
    shifts = (jnp.arange(vpw, dtype=jnp.uint32) * bits)[None, :, None]
    biased = (packed[:, None, :] >> shifts) & mask  # (nb, vpw, w): planar
    code = biased.astype(jnp.int32) - s
    xhat = code.astype(jnp.float32) / s * scale[:, :, None]
    return xhat.reshape(nb, vpw * w).astype(out_dtype)
