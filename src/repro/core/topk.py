"""Bucketed TopK sparsification with error feedback (paper Alg. 2, §8.3).

The paper selects k entries out of every bucket of 512 consecutive gradient
values ("For CIFAR-10 we select k = 8 and 16 entries from every bucket of
512"). Bucketing has a crucial systems property we exploit throughout
(DESIGN.md §2.1): per-index-range counts are EXACTLY uniform, so the
all_to_all split phase of the allreduce needs no dynamic message sizes.
"""
from __future__ import annotations

import functools
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


@functools.partial(jax.jit, static_argnames=("k",))
def _rounds(xb: jax.Array, k: int):
    """Sort-free selection over the last axis of ``xb``.

    The buckets' entries are ordered by (|x| descending, index ascending)
    -- ``lax.top_k``'s order, so the selected set is the same -- and round
    r takes the first entry after round r-1's pick with one variadic
    reduction that carries (key, index, x). Keys are |x|'s f32 bits as
    int32: the floats' order, nan above inf, no flush of subnormals. The
    rounds are a loop, so the chip holds one round's code, not k. An
    entry is selected when it is not after the last pick. The k pairs are
    put in ascending index order by rank (the count of smaller selected
    indices). Jitted, so an eager caller dispatches one program, not the
    ops one by one. Returns (lidx, val, selection mask)."""
    # buckets first: the rows, not the few buckets of a row, tile the
    # second-minor axis of the arrays the loop carries
    xb = jnp.moveaxis(xb, -2, 0)
    ax = xb.ndim - 1
    lead = xb.shape[:-1]
    key = jax.lax.bitcast_convert_type(jnp.abs(xb).astype(jnp.float32),
                                       jnp.int32)
    iota = jax.lax.broadcasted_iota(jnp.int32, xb.shape, ax)

    def first(a, b):
        take = (a[0] > b[0]) | ((a[0] == b[0]) & (a[1] < b[1]))
        return tuple(jax.lax.select(take, p, q) for p, q in zip(a, b))

    def after(kv, j):
        kv, j = kv[..., None], j[..., None]
        return (key < kv) | ((key == kv) & (iota > j))

    init = (jnp.int32(-1), jnp.int32(xb.shape[-1]), jnp.zeros((), xb.dtype))

    def round_(r, carry):
        kv, j, lidx, val = carry
        live = jnp.where(after(kv, j), key, -1)
        kv, j, v = jax.lax.reduce((live, iota, xb), init, first, (ax,))
        return (kv, j, jax.lax.dynamic_update_index_in_dim(lidx, j, r, ax),
                jax.lax.dynamic_update_index_in_dim(val, v, r, ax))

    # everything is after (int32 max, -1): |x|'s bits are at most that
    carry = (jnp.full(lead, jnp.iinfo(jnp.int32).max, jnp.int32),
             jnp.full(lead, -1, jnp.int32),
             jnp.zeros((*lead, k), jnp.int32), jnp.zeros((*lead, k), xb.dtype))
    kv, j, lidx, val = jax.lax.fori_loop(0, k, round_, carry)
    bits = jax.lax.bitcast_convert_type(val, jnp.dtype(
        f"int{8 * jnp.dtype(xb.dtype).itemsize}"))
    rank = jnp.sum(lidx[..., None, :] < lidx[..., :, None], axis=-1)
    slot = rank[..., :, None] == jnp.arange(k, dtype=rank.dtype)  # (.., i, p)
    # slot p takes the one pair of rank p: an integer sum over one live
    # entry moves an index, or a value's bits, unchanged
    lidx = jnp.sum(jnp.where(slot, lidx[..., :, None], 0), axis=-2)
    bits = jnp.sum(jnp.where(slot, bits[..., :, None], 0), axis=-2,
                   dtype=bits.dtype)
    return (jnp.moveaxis(lidx, 0, -2),
            jnp.moveaxis(jax.lax.bitcast_convert_type(bits, xb.dtype), 0, -2),
            jnp.moveaxis(~after(kv, j), 0, -2))


def compress2d(
    x: jax.Array, k_per_bucket: int, bucket_size: int = 512
) -> tuple[BatchedStream, jax.Array]:
    """Batched TopK compression of a canonical (*lead, cols) layout.

    The selection is sort-free (:func:`_rounds`): k rounds of one
    reduction per bucket, the same set as ``lax.top_k``. It operates on the
    last axis only -- the leading dims are never merged or split -- so
    every leading axis keeps whatever sharding it has. Returns (stream,
    residual (*lead, cols))."""
    *lead, cols = x.shape
    b = bucket_size
    assert cols % b == 0, (x.shape, b)
    assert 0 < k_per_bucket <= b, (k_per_bucket, b)
    m = cols // b
    xb = x.reshape(*lead, m, b)
    lidx, val, sel = _rounds(xb, k_per_bucket)
    residual = jnp.where(sel, 0, xb).reshape(*lead, cols)
    return BatchedStream(lidx, val, b), residual
