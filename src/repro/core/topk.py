"""Bucketed TopK sparsification with error feedback (paper Alg. 2, §8.3).

The paper selects k entries out of every bucket of 512 consecutive gradient
values ("For CIFAR-10 we select k = 8 and 16 entries from every bucket of
512"). Bucketing has a crucial systems property we exploit throughout
(DESIGN.md §2.1): per-index-range counts are EXACTLY uniform, so the
all_to_all split phase of the allreduce needs no dynamic message sizes.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.bucket_topk.ops import bucket_topk
from repro.kernels.bucket_scatter.ops import bucket_scatter
from repro.core.sparse_stream import SparseStream


class UniformStream(NamedTuple):
    """A bucket-uniform sparse vector: exactly k entries per B-wide bucket.

    lidx: (nb, k) int32, ascending within bucket, values in [0, B)
    val:  (nb, k)
    Global index of entry (r, j) = r * B + lidx[r, j]; total length nb * B.
    """

    lidx: jax.Array
    val: jax.Array
    bucket_size: int

    @property
    def num_buckets(self) -> int:
        return self.lidx.shape[0]

    @property
    def k(self) -> int:
        return self.lidx.shape[1]

    @property
    def n(self) -> int:
        return self.num_buckets * self.bucket_size

    @property
    def nnz(self) -> int:
        return self.lidx.shape[0] * self.lidx.shape[1]

    def to_stream(self) -> SparseStream:
        """Flat global-index stream (sorted: buckets are contiguous)."""
        nb, k = self.lidx.shape
        gidx = (jnp.arange(nb, dtype=jnp.int32)[:, None] * self.bucket_size
                + self.lidx)
        return SparseStream(
            idx=gidx.reshape(-1),
            val=self.val.reshape(-1),
            nnz=jnp.asarray(nb * k, jnp.int32),
        )

    def densify(self, impl: str = "auto") -> jax.Array:
        return bucket_scatter(self.lidx, self.val, self.bucket_size, impl=impl).reshape(-1)


def compress(
    x: jax.Array, k_per_bucket: int, bucket_size: int = 512, impl: str = "auto"
) -> tuple[UniformStream, jax.Array]:
    """TopK-compress a flat vector. Returns (stream, residual).

    x is zero-padded up to a bucket multiple; padding positions always lose
    the top-k race only if real values beat them (zeros may be selected in
    degenerate all-zero buckets — harmless: their value is 0).
    residual = x - densify(stream) restricted to the original length.
    """
    (n,) = x.shape
    nb = -(-n // bucket_size)
    pad = nb * bucket_size - n
    xp = jnp.pad(x, (0, pad)) if pad else x
    val, lidx, res = bucket_topk(xp.reshape(nb, bucket_size), k_per_bucket, impl=impl)
    stream = UniformStream(lidx, val, bucket_size)
    residual = res.reshape(-1)[:n]
    return stream, residual


class BatchedStream(NamedTuple):
    """Bucket-uniform stream with leading batch axes that are NEVER
    reshaped away — so a 'model'-sharded canonical row axis (and, in the
    auto-SPMD fallback, a leading replica axis) rides through compression
    and the data-axis collectives untouched (flattening it forced a
    full-gradient all-gather over TP; found via dry-run HLO).

    lidx/val: (*lead, m, k) — lead batch dims (sharded ok), m buckets each.
    """

    lidx: jax.Array
    val: jax.Array
    bucket_size: int

    @property
    def k(self) -> int:
        return self.lidx.shape[-1]

    def densify(self) -> jax.Array:
        """(*lead, m*B) via batched one-hot contraction (k small)."""
        *lead, m, k = self.lidx.shape
        b = self.bucket_size
        iota = jnp.arange(b, dtype=jnp.int32)
        onehot = (self.lidx[..., None] == iota).astype(self.val.dtype)
        dense = jnp.einsum("...mkb,...mk->...mb", onehot, self.val)
        return dense.reshape(*lead, m * b)


def compress2d(
    x: jax.Array, k_per_bucket: int, bucket_size: int = 512
) -> tuple[BatchedStream, jax.Array]:
    """Batched TopK compression of a canonical (*lead, cols) layout.

    Pure batched-jnp (top_k/sort/take_along_axis operate on the last
    axis only — the leading dims are never merged or split), so every
    leading axis keeps whatever sharding it has. Returns
    (stream, residual (*lead, cols))."""
    *lead, cols = x.shape
    b = bucket_size
    assert cols % b == 0, (x.shape, b)
    m = cols // b
    xb = x.reshape(*lead, m, b)
    mag = jnp.abs(xb)
    _, order = jax.lax.top_k(mag, k_per_bucket)              # (*lead, m, k)
    lidx = jnp.sort(order, axis=-1).astype(jnp.int32)
    val = jnp.take_along_axis(xb, lidx, axis=-1)
    iota = jnp.arange(b, dtype=jnp.int32)
    sel = jnp.any(lidx[..., None] == iota, axis=-2)          # (*lead, m, b)
    residual = jnp.where(sel, 0, xb).reshape(*lead, cols)
    return BatchedStream(lidx, val, b), residual
