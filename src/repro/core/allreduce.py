"""SparCML sparse allreduce algorithms (paper §5.3) as JAX collectives.

All ``*_inside`` functions run INSIDE ``jax.shard_map`` over a named mesh
axis (the data-parallel axis). Standalone jit-level wrappers at the bottom
build the shard_map for tests/benchmarks.

Algorithms (see DESIGN.md §2.1 for the MPI->ICI mapping):

  ssar_recursive_double   log2(P) rounds of XOR-partner ppermute + sparse
                          merge; capacity doubles per round following the
                          paper's |H1|+|H2| bound; switches to a dense
                          tail when the bound crosses the delta threshold.
  ssar_split_allgather    all_to_all split by index range (sparse
                          reduce-scatter), local merge, sparse allgather
                          (concatenation — ranges are disjoint).
  dsar_split_allgather    split phase as above, then DENSIFY the owned
                          range (bucket_scatter kernel) and run a dense
                          allgather, optionally QSGD-quantized (paper §6).
  ssar_balanced_split     Ok-Top-k-style balanced split-and-gather
                          (DESIGN.md §9): split as above, owner-local
                          re-top-k to (k/P)(1+eps) items, allgather at
                          that fixed capacity — O(k) per-node traffic;
                          clamped-off mass returns as an EF fold.
  ssar_rearranged_rs      SparDL-style rearranged reduce-scatter
                          (DESIGN.md §9): log2(P) recursive-halving
                          rounds in stream form end-to-end (no densify
                          between phases) + capacity-clamped allgather;
                          every clamp drop folds into the EF residual
                          (the global-residual rule).
  dense_allreduce         psum (the Cray-MPI/NCCL baseline).

The bucket-uniform fast path (k entries per 512-bucket, paper §8.3) routes
the split phase with pure reshapes — zero sorting, exact slot sizes.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import sparse_stream as ss
from repro.core.sparse_stream import SENTINEL, SparseStream
from repro.core.topk import UniformStream
from repro.core.qsgd import QSGDConfig, quantize, dequantize
from repro.core.cost_model import (
    balanced_shard_cap,
    rearranged_round_caps,
    select_algorithm,
)
from repro.kernels.bucket_scatter.ops import bucket_scatter
from repro.obs import scopes


@dataclass(frozen=True)
class ReduceOut:
    """Static union: exactly one of (stream, dense) is set (trace-time)."""

    stream: Optional[SparseStream] = None
    dense: Optional[jax.Array] = None

    def to_dense(self, n: int) -> jax.Array:
        if self.dense is not None:
            return self.dense
        return ss.densify(self.stream, n)


def _xor_perm(p: int, dist: int) -> list[tuple[int, int]]:
    return [(i, i ^ dist) for i in range(p)]


def _exchange(stream: SparseStream, axis_name: str, perm) -> SparseStream:
    idx, val, nnz = jax.lax.ppermute(
        (stream.idx, stream.val, stream.nnz), axis_name, perm
    )
    return SparseStream(idx, val, nnz)


# --------------------------------------------------------------------------
# SSAR_Recursive_double (paper §5.3.1)
# --------------------------------------------------------------------------

def ssar_recursive_double_inside(
    stream: SparseStream,
    *,
    axis_name: str,
    p: int,
    n: int,
    delta: int | None = None,
    cap_max: int | None = None,
) -> ReduceOut:
    """Recursive doubling over an axis of size p (power of two).

    Capacity schedule: after round t the fill-in bound is k*2^(t+1)
    (paper §5.1 uses the same |H1|+|H2| bound at runtime). When the bound
    crosses ``delta`` the representation switches to dense for the remaining
    rounds (pairwise dense exchange+add keeps partial-group sums correct).
    """
    assert p & (p - 1) == 0, "P must be a power of two (paper assumption 2)"
    if delta is None:
        delta = ss.delta_threshold(n, jnp.dtype(stream.val.dtype).itemsize)
    if cap_max is None:
        cap_max = min(n, delta)
    rounds = int(math.log2(p))
    dense: jax.Array | None = None
    for t in range(rounds):
        perm = _xor_perm(p, 1 << t)
        if dense is not None:
            other = jax.lax.ppermute(dense, axis_name, perm)
            dense = dense + other
            continue
        cap_next = min(2 * stream.capacity, cap_max)
        if 2 * stream.capacity > delta:
            # Dynamic fill-in: switch to dense (paper §5.3.3) for the tail.
            dense = ss.densify(stream, n)
            other = jax.lax.ppermute(dense, axis_name, perm)
            dense = dense + other
            stream = None
            continue
        other = _exchange(stream, axis_name, perm)
        stream = ss.merge(stream, other, cap_next)
    return ReduceOut(stream=stream, dense=dense)


# --------------------------------------------------------------------------
# Split phase (shared by SSAR/DSAR _Split_allgather), uniform fast path
# --------------------------------------------------------------------------

def _split_uniform(u: UniformStream, axis_name: str, p: int):
    """Route bucket rows to their owning range via pure reshape + a2a.

    Range r owns bucket rows [r*nb/p, (r+1)*nb/p). Returns (lidx, val) of
    shape (p, nb/p, k): contribution of every source rank to MY rows.
    """
    nb, k = u.lidx.shape
    assert nb % p == 0, f"buckets ({nb}) must divide by P ({p})"
    lidx = u.lidx.reshape(p, nb // p, k)
    val = u.val.reshape(p, nb // p, k)
    lidx = jax.lax.all_to_all(lidx, axis_name, split_axis=0, concat_axis=0, tiled=True)
    val = jax.lax.all_to_all(val, axis_name, split_axis=0, concat_axis=0, tiled=True)
    return lidx.reshape(p, nb // p, k), val.reshape(p, nb // p, k)


def _reduce_range_dense(lidx, val, bucket_size: int, impl: str = "auto") -> jax.Array:
    """Densify the received (p, rows, k) contributions into my range."""
    p, rows, k = lidx.shape
    dense = bucket_scatter(
        lidx.reshape(p * rows, k), val.reshape(p * rows, k), bucket_size, impl=impl
    )
    return dense.reshape(p, rows * bucket_size).sum(axis=0)


# --------------------------------------------------------------------------
# SSAR_Split_allgather (paper §5.3.2)
# --------------------------------------------------------------------------

def ssar_split_allgather_inside(
    u: UniformStream,
    *,
    axis_name: str,
    p: int,
    range_cap: int | None = None,
) -> SparseStream:
    """Sparse reduce-scatter (split) + sparse allgather (concatenation).

    Returns a global SparseStream of capacity p * range_cap. Merging within
    the owned range uses the sort+combine path; ranges are disjoint so the
    allgather is plain concatenation (paper §5.1).
    """
    nb, k = u.lidx.shape
    b = u.bucket_size
    lidx, val = _split_uniform(u, axis_name, p)
    rows = nb // p
    # Global indices within my range, relative to range start.
    row_off = jax.lax.broadcasted_iota(jnp.int32, (p, rows, k), 1) * b
    rel = (lidx + row_off).reshape(-1)
    vals = val.reshape(-1)
    local = SparseStream(rel, vals, jnp.asarray(rel.shape[0], jnp.int32))
    if range_cap is None:
        range_cap = min(p * rows * k, rows * b)
    merged = ss.merge(local, ss.empty(0, vals.dtype), range_cap)  # sort+combine
    # Rebase to global index space: my range starts at rank * rows * b.
    my_rank = jax.lax.axis_index(axis_name)
    base = (my_rank * rows * b).astype(jnp.int32)
    gidx = jnp.where(merged.idx == SENTINEL, SENTINEL, merged.idx + base)
    # Sparse allgather = concatenation of disjoint ranges.
    all_idx = jax.lax.all_gather(gidx, axis_name, tiled=True)
    all_val = jax.lax.all_gather(merged.val, axis_name, tiled=True)
    total_nnz = jax.lax.psum(merged.nnz, axis_name)
    return SparseStream(all_idx, all_val, total_nnz)


# --------------------------------------------------------------------------
# Near-optimal portfolio (DESIGN.md §9): capacity-clamped algorithms.
# Both return (dense sum, fold): ``fold`` is the pre-scale mass this rank
# clamped off the wire, to be added into its EF residual by the executor —
# the SparDL "global residual" rule. Under non-binding caps (e.g. full
# index overlap) fold == 0 and the result equals the dense reference.
# --------------------------------------------------------------------------


def _top_cap_indices(mag: jax.Array, cap: int) -> jax.Array:
    """Indices of the ``cap`` largest magnitudes (ties -> lower index,
    so every rank picks deterministically)."""
    _, idx = jax.lax.top_k(mag, cap)
    return idx


def _take_top_stream(s: SparseStream, mask: jax.Array, cap: int):
    """Top-``cap``-|value| masked entries of ``s``, plus the clamped rest.

    Returns (kept stream of capacity ``cap`` sorted by index, (drop_idx,
    drop_val) SENTINEL-padded arrays of the masked entries past the cap).
    Magnitude ties break toward the lower index (streams are index-sorted
    and the argsort is stable), deterministic across ranks."""
    cap = min(cap, s.capacity)
    neg = jnp.where(mask, -jnp.abs(s.val), jnp.inf)
    order = jnp.argsort(neg, stable=True)   # masked first, big |v| first
    idx_o, val_o, m_o = s.idx[order], s.val[order], mask[order]
    sel_i = jnp.where(m_o[:cap], idx_o[:cap], SENTINEL)
    sel_v = jnp.where(m_o[:cap], val_o[:cap], 0)
    sel_i, sel_v = jax.lax.sort((sel_i, sel_v), num_keys=1)
    nnz = jnp.minimum(jnp.sum(mask), cap).astype(jnp.int32)
    drop_i = jnp.where(m_o[cap:], idx_o[cap:], SENTINEL)
    drop_v = jnp.where(m_o[cap:], val_o[cap:], 0)
    return SparseStream(sel_i, sel_v, nnz), (drop_i, drop_v)


def ssar_balanced_split_inside(
    u: UniformStream,
    *,
    axis_name: str,
    p: int,
    impl: str = "auto",
    scatter: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Balanced split-and-gather (Ok-Top-k style, DESIGN.md §9).

    Split phase: the bucket-uniform a2a route (exactly balanced by
    construction — every rank receives (P-1)/P * k items, the O(k)
    balance bound with eps=0). Owner phase: scatter-add the received
    contributions into my range, then re-top-k to the
    ``balanced_shard_cap`` capacity. Gather phase: allgather the clamped
    (idx, val) shards — (P-1) * cap items instead of split_allgather's
    O(kP) worst-case range union. Returns (dense (n,), fold (n,)): fold
    carries my range's clamped-off partial sums (zero when the cap does
    not bind, e.g. full index overlap).

    ``scatter`` (DESIGN.md §11) terminates at the owner shard: the
    gather phase — the capped allgather, which is exactly what the wire
    saves — is SKIPPED and the return is (shard (n/p,), fold (n,)).
    Bit-parity by construction: owned ranges are disjoint, so the
    replicated dense restricted to my range IS the clamped shard; the
    re-top-k and its fold are kept so EF trajectories match the
    replicated mode exactly."""
    nb, k = u.lidx.shape
    b = u.bucket_size
    n = nb * b
    lidx, val = _split_uniform(u, axis_name, p)
    shard = _reduce_range_dense(lidx, val, b, impl=impl)   # (n/p,) owner sums
    range_n = shard.shape[0]
    cap = min(balanced_shard_cap(nb * k, p, n), range_n)
    sel_idx = _top_cap_indices(jnp.abs(shard), cap)
    sel_val = shard[sel_idx]
    selected = jnp.zeros_like(shard).at[sel_idx].set(sel_val)
    my_rank = jax.lax.axis_index(axis_name)
    base = (my_rank * range_n).astype(jnp.int32)
    fold = jax.lax.dynamic_update_slice(
        jnp.zeros((n,), shard.dtype), shard - selected, (base,))
    if scatter:
        return selected, fold
    gidx = sel_idx.astype(jnp.int32) + base
    all_idx = jax.lax.all_gather(gidx, axis_name, tiled=True)   # (p*cap,)
    all_val = jax.lax.all_gather(sel_val, axis_name, tiled=True)
    dense = jnp.zeros((n,), shard.dtype).at[all_idx].add(all_val, mode="drop")
    return dense, fold


def ssar_rearranged_rs_inside(
    u: UniformStream,
    *,
    axis_name: str,
    p: int,
    scatter: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Rearranged reduce-scatter + allgather (SparDL style, DESIGN.md §9).

    log2(P) recursive-halving rounds: each round partitions my current
    index range at its midpoint, ships the partner's half as a stream
    (ppermute), and merges the received half — stream form end-to-end,
    no densify between phases. Capacities follow
    ``rearranged_round_caps``; entries past a send/merge cap are the
    smallest-magnitude ones and are accumulated into ``fold`` at their
    global coordinate (the global-residual rule) instead of being lost.
    Final phase: allgather of the disjoint owned shards. Returns
    (dense (n,), fold (n,)).

    ``scatter`` (DESIGN.md §11): the MSB-first halving ends rank r
    holding exactly the owned range [r*n/p, (r+1)*n/p) — the natural
    reduce-scatter. The final allgather is skipped and the return is
    (shard (n/p,), fold (n,)), densified at range-local coordinates;
    rounds and caps are untouched, so folds and numerics match the
    replicated mode restricted to the owned range."""
    assert p & (p - 1) == 0, "P must be a power of two (paper assumption 2)"
    nb, kpb = u.lidx.shape
    n = u.n
    caps = rearranged_round_caps(nb * kpb, n, p)
    s = u.to_stream()
    my_rank = jax.lax.axis_index(axis_name)
    fold = jnp.zeros((n,), s.val.dtype)
    lo = jnp.zeros((), jnp.int32)
    length = n
    for t, (send_cap, merged_cap) in enumerate(caps):
        dist = p >> (t + 1)
        perm = _xor_perm(p, dist)
        half = length // 2
        mid = lo + half
        keep_lower = (my_rank & dist) == 0      # MSB-first: rank r ends
        valid = s.idx != SENTINEL               # owning [r*n/p, (r+1)*n/p)
        in_lower = s.idx < mid
        send_mask = valid & (in_lower ^ keep_lower)
        keep_mask = valid & ~(in_lower ^ keep_lower)
        # Keep side stays at full capacity (no clamp, no drop) — only the
        # wire and the merged result are capacity-bound.
        kept = SparseStream(jnp.where(keep_mask, s.idx, SENTINEL),
                            jnp.where(keep_mask, s.val, 0),
                            jnp.sum(keep_mask).astype(jnp.int32))
        sent, (sd_i, sd_v) = _take_top_stream(s, send_mask, send_cap)
        fold = fold.at[sd_i].add(sd_v, mode="drop")
        recv = _exchange(sent, axis_name, perm)
        merged = ss.merge(kept, recv, kept.capacity + recv.capacity)
        clamped, (md_i, md_v) = _take_top_stream(
            merged, merged.idx != SENTINEL, merged_cap)
        fold = fold.at[md_i].add(md_v, mode="drop")
        s = clamped
        lo = jnp.where(keep_lower, lo, mid).astype(jnp.int32)
        length = half
    if scatter:
        # Owner-local densify at range-relative coordinates; SENTINEL
        # entries land far past n/p and drop. lo == my_rank * n/p here.
        shard = jnp.zeros((n // p,), s.val.dtype).at[s.idx - lo].add(
            s.val, mode="drop")
        return shard, fold
    # Owned ranges are disjoint: the allgather is plain concatenation and
    # the scatter-add places each shard at its global coordinates.
    all_idx = jax.lax.all_gather(s.idx, axis_name, tiled=True)
    all_val = jax.lax.all_gather(s.val, axis_name, tiled=True)
    dense = jnp.zeros((n,), s.val.dtype).at[all_idx].add(all_val, mode="drop")
    return dense, fold


# --------------------------------------------------------------------------
# DSAR_Split_allgather (paper §5.3.3 + §6 low-precision second phase)
# --------------------------------------------------------------------------

def dsar_split_allgather_inside(
    u: UniformStream,
    *,
    axis_name: str,
    p: int,
    qsgd: QSGDConfig | None = None,
    rand: jax.Array | None = None,
    out_dtype=jnp.float32,
    impl: str = "auto",
) -> jax.Array:
    """Split phase sparse, owned range densified, dense (optionally
    QSGD-quantized) allgather. Returns the dense global sum (n,)."""
    nb, k = u.lidx.shape
    b = u.bucket_size
    lidx, val = _split_uniform(u, axis_name, p)
    shard = _reduce_range_dense(lidx, val, b, impl=impl)  # (nb/p * b,)
    if qsgd is None:
        full = jax.lax.all_gather(shard.astype(out_dtype), axis_name, tiled=True)
        return full
    if rand is None:
        raise ValueError("QSGD second phase needs stochastic-rounding bits")
    packed, scale = quantize(shard, qsgd, rand.reshape(-1)[: shard.shape[0]], impl=impl)
    packed_all = jax.lax.all_gather(packed, axis_name, tiled=True)
    scale_all = jax.lax.all_gather(scale, axis_name, tiled=True)
    return dequantize(packed_all, scale_all, qsgd, nb * b, out_dtype, impl=impl)


# --------------------------------------------------------------------------
# Batched DSAR: leading row axis (e.g. 'model'-sharded canonical rows)
# rides through the data-axis collectives as a pure batch dim.
# --------------------------------------------------------------------------

def _qsgd_roundtrip(x2d, rand2d, qsgd: QSGDConfig, impl: str, out_dtype):
    """quantize -> dequantize (the wire fidelity without the wire)."""
    from repro.kernels.qsgd_pack.ops import qsgd_pack
    from repro.kernels.qsgd_unpack.ops import qsgd_unpack

    with scopes.scope(scopes.QSGD):
        packed, scale = qsgd_pack(x2d, rand2d, qsgd.bits, qsgd.scale_mode,
                                  impl=impl)
        return qsgd_unpack(packed, scale, qsgd.bits, out_dtype, impl=impl)


def dsar_split_allgather_batched_inside(
    u,  # BatchedStream: lidx/val (r, m, k)
    *,
    axis_name: str,
    p: int,
    qsgd: QSGDConfig | None = None,
    rand: jax.Array | None = None,
    out_dtype=jnp.float32,
    impl: str = "auto",
    coll=None,  # repro.comm.collectives.CollectiveContext | None (native)
    scatter: bool = False,
) -> jax.Array:
    """DSAR over the 'data' axis with a batched row dim. Returns (r, m*B),
    or the (r, m*B/p) owned column shard when ``scatter`` (DESIGN.md §11).

    Native lowering — ONE collective per phase:
      split: single fused a2a on the BUCKET axis (axis 1) carrying
             [val | lidx-as-f32] (lidx < B <= 512 is exact in f32);
      densify my bucket range (batched one-hot contraction);
      gather: single all_gather on axis 1 ([packed-bitcast-f32 | scale]
              when QSGD-quantized). ``scatter`` SKIPS the gather — the
      quantize->dequantize round-trip runs locally on my shard with my
      rand bits, so the shard is bit-equal to the replicated result
      restricted to my columns.

    Emulated lowering (coll.native=False — partial-manual regions on
    backends where only psum lowers, DESIGN.md §4): the full dense sum in
    one psum, then the identical per-range QSGD quantize->dequantize
    applied locally by every rank; ``scatter`` slices my range off the
    replicated result (exact parity, no wire saving — scaffolding only).
    Bit-identical results to the native path given the same per-range
    rand bits.

    rand: stochastic-rounding bits for the QSGD phase — my shard's
    (r*m*B/p,) u32 when native, all ranges' (p, r*m*B/p) when emulated
    (every rank replays every owner's rounding).
    """
    r, m, k = u.lidx.shape
    b = u.bucket_size
    assert m % p == 0, f"buckets-per-row {m} % p {p}"
    mp = m // p
    shard_cols = mp * b

    if coll is None:
        from repro.comm.collectives import CollectiveContext  # lazy: no cycle
        coll = CollectiveContext(axis_name, p)

    if not coll.native:
        dense = coll.psum(u.densify().astype(jnp.float32))   # (r, m*B)
        if qsgd is None:
            out = dense
        else:
            if rand is None:
                raise ValueError(
                    "QSGD second phase needs stochastic-rounding bits")
            bq = qsgd.bucket_size
            nbq = shard_cols // bq
            # (r, m*B) -> per-range rows exactly as each native owner sees
            xs = dense.reshape(r, p, shard_cols).transpose(1, 0, 2)
            xhat = _qsgd_roundtrip(
                xs.reshape(p * r * nbq, bq),
                rand.reshape(p * r * nbq, bq), qsgd, impl, jnp.float32)
            out = (xhat.reshape(p, r, shard_cols).transpose(1, 0, 2)
                   .reshape(r, m * b))
        if scatter:
            mine = jax.lax.dynamic_slice_in_dim(
                out.reshape(r, p, shard_cols),
                coll.axis_rank(), 1, axis=1)
            return mine.reshape(r, shard_cols).astype(out_dtype)
        return out.astype(out_dtype)

    assert b <= 1 << 24, "lidx-as-f32 wire format needs exact f32 ints"
    payload = jnp.concatenate(
        [u.val.astype(jnp.float32), u.lidx.astype(jnp.float32)], axis=-1)
    payload = coll.all_to_all(payload, axis=1)               # ONE a2a
    payload = payload.reshape(r, p, mp, 2 * k)
    val = payload[..., :k]
    lidx = payload[..., k:].astype(jnp.int32)
    # densify my bucket range and reduce over the p sources
    iota = jnp.arange(b, dtype=jnp.int32)
    onehot = (lidx[..., None] == iota).astype(jnp.float32)
    shard = jnp.einsum("rpmkb,rpmk->rmb", onehot, val).reshape(r, shard_cols)
    if scatter:
        # Stop at the owner shard: the gather phase never happens. With
        # QSGD the quantize->dequantize round-trip still runs (locally,
        # my rand bits) so the shard is bit-equal to the replicated
        # result restricted to my columns — wire fidelity without wire.
        if qsgd is None:
            return shard.astype(out_dtype)
        if rand is None:
            raise ValueError(
                "QSGD second phase needs stochastic-rounding bits")
        bq = qsgd.bucket_size
        nbq = shard_cols // bq
        xhat = _qsgd_roundtrip(
            shard.reshape(r * nbq, bq),
            rand.reshape(-1)[: r * nbq * bq].reshape(r * nbq, bq),
            qsgd, impl, jnp.float32)
        return xhat.reshape(r, shard_cols).astype(out_dtype)
    if qsgd is None:
        return coll.all_gather(shard.astype(out_dtype), axis=1)
    if rand is None:
        raise ValueError("QSGD second phase needs stochastic-rounding bits")
    from repro.kernels.qsgd_pack.ops import qsgd_pack
    from repro.kernels.qsgd_unpack.ops import qsgd_unpack

    bq = qsgd.bucket_size
    nbq = shard_cols // bq
    with scopes.scope(scopes.QSGD):
        packed, scale = qsgd_pack(
            shard.reshape(r * nbq, bq),
            rand.reshape(-1)[: r * nbq * bq].reshape(r * nbq, bq),
            qsgd.bits, qsgd.scale_mode, impl=impl)
        w = packed.shape[-1]
        # ONE gather: [packed u32 bitcast to f32 | scale f32] along axis 1
        wire = jnp.concatenate(
            [jax.lax.bitcast_convert_type(packed.reshape(r, nbq * w),
                                          jnp.float32),
             scale.reshape(r, nbq)], axis=1)
    wire = coll.all_gather(wire, axis=1).reshape(r, p, nbq * w + nbq)
    with scopes.scope(scopes.QSGD):
        packed_all = jax.lax.bitcast_convert_type(
            wire[..., : nbq * w], jnp.uint32)
        scale_all = wire[..., nbq * w:]
        xhat = qsgd_unpack(packed_all.reshape(r * p * nbq, w),
                           scale_all.reshape(r * p * nbq, 1), qsgd.bits,
                           jnp.float32, impl=impl)
    # received order is (r, p, shard) — identical to the pre-fusion
    # two-gather layout, so the reshape back to (r, m*B) is unchanged
    return xhat.reshape(r, m * b).astype(out_dtype)


# --------------------------------------------------------------------------
# Dispatcher + dense baseline
# --------------------------------------------------------------------------

def safe_psum(x: jax.Array, axis_name) -> jax.Array:
    """psum with an f32 round-trip for 16-bit operands.

    Works around an XLA-CPU partitioner bug in this JAX build: bf16/f16
    reductions inside a PARTIAL-manual shard_map (auto axes present) build
    an invalid binary 'copy' HLO and abort. 32-bit reductions are fine;
    real TPU backends don't hit this path (documented in DESIGN.md §5).
    """
    if x.dtype in (jnp.bfloat16, jnp.float16):
        return jax.lax.psum(x.astype(jnp.float32), axis_name).astype(x.dtype)
    return jax.lax.psum(x, axis_name)


def safe_pmean(x: jax.Array, axis_name) -> jax.Array:
    if x.dtype in (jnp.bfloat16, jnp.float16):
        return jax.lax.pmean(x.astype(jnp.float32), axis_name).astype(x.dtype)
    return jax.lax.pmean(x, axis_name)


def dense_allreduce_inside(x: jax.Array, *, axis_name: str) -> jax.Array:
    return safe_psum(x, axis_name)


def sparse_allreduce_inside(
    u: UniformStream,
    *,
    axis_name: str,
    p: int,
    algorithm: str = "auto",
    qsgd: QSGDConfig | None = None,
    rand: jax.Array | None = None,
    out_dtype=jnp.float32,
    impl: str = "auto",
) -> ReduceOut:
    """Reduce a bucket-uniform stream over the axis; auto-selects the
    algorithm from the alpha-beta cost model + expected fill-in (trace time,
    mirroring the paper's guidance that the user knows K roughly)."""
    n = u.n
    if algorithm == "auto":
        algorithm = select_algorithm(
            p, u.nnz, n, value_bits=(qsgd.bits if qsgd else 32)
        )
    if algorithm == "dense":
        return ReduceOut(dense=dense_allreduce_inside(u.densify(impl=impl), axis_name=axis_name))
    if algorithm == "ssar_recursive_double":
        return ssar_recursive_double_inside(
            u.to_stream(), axis_name=axis_name, p=p, n=n
        )
    if algorithm == "ssar_split_allgather":
        return ReduceOut(stream=ssar_split_allgather_inside(u, axis_name=axis_name, p=p))
    if algorithm == "ssar_balanced_split":
        # Standalone wrapper: no EF residual to fold the clamp drops into
        # (the plan executor keeps them); under non-binding caps fold==0.
        dense, _fold = ssar_balanced_split_inside(
            u, axis_name=axis_name, p=p, impl=impl)
        return ReduceOut(dense=dense)
    if algorithm == "ssar_rearranged_rs":
        dense, _fold = ssar_rearranged_rs_inside(u, axis_name=axis_name, p=p)
        return ReduceOut(dense=dense)
    if algorithm == "dsar_split_allgather":
        return ReduceOut(
            dense=dsar_split_allgather_inside(
                u, axis_name=axis_name, p=p, qsgd=qsgd, rand=rand,
                out_dtype=out_dtype, impl=impl,
            )
        )
    raise ValueError(f"unknown algorithm {algorithm!r}")


# --------------------------------------------------------------------------
# Standalone jit-level wrappers (tests / benchmarks / examples)
# --------------------------------------------------------------------------

def make_sparse_allreduce(
    mesh: jax.sharding.Mesh,
    axis_name: str,
    n: int,
    k_per_bucket: int,
    bucket_size: int = 512,
    algorithm: str = "auto",
    qsgd: QSGDConfig | None = None,
    impl: str = "auto",
):
    """Returns f(x_batched (P, n), rand (P, nbq*bq) u32|None) -> dense (n,)
    summing per-rank vectors with TopK compression + sparse allreduce.

    x rows live on distinct ranks (sharded over axis_name); the result is
    replicated. For benchmarks and the MPI-OPT-style examples.
    """
    from jax.sharding import PartitionSpec as P  # local import, avoids cycle
    from repro.compat import shard_map
    from repro.core import topk as topk_mod

    p = mesh.shape[axis_name]

    def inner(x, rand):
        x = x.reshape(-1)  # my row
        u, _res = topk_mod.compress(x, k_per_bucket, bucket_size, impl=impl)
        out = sparse_allreduce_inside(
            u, axis_name=axis_name, p=p, algorithm=algorithm,
            qsgd=qsgd, rand=rand.reshape(-1) if rand is not None else None,
            out_dtype=x.dtype, impl=impl,
        )
        return out.to_dense(u.n)[:n]

    spec_x = P(axis_name)
    spec_r = P(axis_name) if qsgd is not None else None
    in_specs = (spec_x, spec_r)
    return jax.jit(
        shard_map(
            inner, mesh=mesh, in_specs=in_specs, out_specs=P(None),
            check_vma=False,
        )
    )
