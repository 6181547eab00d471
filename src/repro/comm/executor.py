"""Plan executor: one TopK-compress + sparse allreduce per bucket.

Runs INSIDE the training shard_map (manual over the dp axes). For each
group the leaves are fused into one canonical buffer (pure reshapes),
then per fusion bucket:

    residual  +=  bucket slice            (error feedback, Alg. 2 line 1)
    stream, residual' = bucketed TopK     (Alg. 2 line 2)
    reduced   = <bucket's algorithm>      (Alg. 2 line 3 — ONE planned
                                           collective pipeline per bucket)
    [+ dense psum over the pod axis — hierarchical, DCN traffic already
       compressed by the within-pod reduction]

Dense buckets (below ``min_sparse_size`` or cost-model-selected) skip
compression and ride a single psum — still fused, still one collective.

Error-feedback state is keyed by bucket name (``plan.residual_shapes``):
the bucket is the unit of compression, so it is the unit of feedback.

The collective flavor (native vs psum-emulated, DESIGN.md §4) arrives via
``native`` + the rank feeds; SSAR algorithms need native collectives and
fall back to DSAR when emulated (same dense result, different wire path).

The pipeline is split into compose-able halves (DESIGN.md §6): the
REDUCE half (``reduce_buckets`` / ``reduce_buckets_spmd``) produces
name-keyed reduced bucket buffers plus the new EF residuals, the APPLY
half (``apply_buckets`` / ``apply_buckets_spmd``) unpacks them back to
leaf layouts. ``execute_plan*`` is the synchronous composition; the
non-blocking runtime (``repro/runtime``) holds the reduced buffers in
flight for one step between the halves.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.comm.buckets import pack_group, unpack_group
from repro.comm.collectives import CollectiveContext
from repro.comm.plan import SyncPlan
from repro.obs import scopes

# repro.core is imported lazily inside the functions below: core/__init__
# re-exports core.compressor, which imports comm — see plan.py.


def _qsgd_rand(key, bucket_idx: int, coll: CollectiveContext,
               pod_rank, shard_elems: int, p: int):
    """Stochastic-rounding bits for one bucket's QSGD phase.

    Native: my shard's bits, keyed by (step key, bucket, my data rank[,
    pod rank]). Emulated: every range's bits stacked (p, shard) — each
    rank replays every owner's rounding on the replicated psum result, so
    the emulated output is bit-identical to the native wire."""
    with scopes.scope(scopes.QSGD):
        sub = jax.random.fold_in(key, bucket_idx)
        if pod_rank is not None:
            sub = jax.random.fold_in(sub, pod_rank)
        if coll.native:
            sub = jax.random.fold_in(sub, coll.axis_rank())
            return jax.random.bits(sub, (shard_elems,), dtype=jnp.uint32)
        return jnp.stack([
            jax.random.bits(jax.random.fold_in(sub, j), (shard_elems,),
                            dtype=jnp.uint32)
            for j in range(p)
        ])


def _bucket_telemetry(out, plan, group, b, p_data: int, p_pod: int,
                      coll: Optional[CollectiveContext] = None,
                      mass: Optional[jax.Array] = None):
    """In-graph per-bucket stats (DESIGN.md §7, §10.5): a (2,) f32 vector
    of [post-reduction nnz, modeled wire bytes at the measured nnz] — or,
    when ``mass`` is supplied, a (4,) vector extended with
    [compressed-mass coverage, EF-residual norm]. The nnz count runs on
    the already-materialized reduced buffer — O(n) local work, no
    collectives — and is replicated across ranks because the buffer is.
    Scattered manual lowerings are the exception: ``out`` is my owned
    shard only, so the global nnz is one scalar psum over the disjoint
    shards (``coll`` supplies it; the SPMD formulation sees the full
    buffer and needs none). The adaptive controller windows these on the
    host. Emitted for EF (compressed) buckets only: raw-dense buckets
    have no replan freedom, so their stats could never influence a
    decision.

    ``mass`` is the globally-summed (3,) vector
    [Σ‖topk‖², Σ‖g+r‖², Σ‖r'‖²] (callers psum it where the formulation
    is per-rank): coverage = ‖topk‖²/‖g+r‖² — the fraction of
    pre-compression gradient mass the wire actually carried this step —
    and ef_norm = ‖r'‖₂, the post-step residual magnitude the health
    engine watches for EF blowup. An all-zero accumulator counts as full
    coverage (nothing to carry)."""
    from repro.core.cost_model import bucket_wire_bytes, pod_wire_bytes

    cfg = plan.cfg
    nnz = jnp.count_nonzero(out).astype(jnp.float32)
    if plan.scattered and coll is not None:
        nnz = coll.psum(nnz)
    k = plan.bucket_k(group, b)
    vb = cfg.qsgd_bits if cfg.qsgd_bits is not None else 32
    wire = bucket_wire_bytes(b.algorithm, p_data, k, b.n, nnz=nnz,
                             value_bits=vb, scattered=plan.scattered)
    if p_pod > 1:
        sparse_pod = b.pod_sparse and group.rows == 1
        wire = wire + pod_wire_bytes(p_pod, b.n, min(b.n, p_data * k),
                                     pod_sparse=sparse_pod)
    base = jnp.stack([nnz, jnp.asarray(wire, jnp.float32)])
    if mass is None:
        return base
    coverage = jnp.where(mass[1] > 0,
                         mass[0] / jnp.maximum(mass[1], jnp.float32(1e-30)),
                         jnp.float32(1.0))
    ef_norm = jnp.sqrt(mass[2])
    return jnp.concatenate([base, jnp.stack([coverage, ef_norm])])


def _local_mass(u_val, acc, residual) -> jax.Array:
    """Per-rank (3,) f32 [Σ‖topk‖², Σ‖g+r‖², Σ‖r'‖²] — the summands of
    the mass-coverage/EF-norm telemetry. Sums over EVERY axis so the
    same helper serves the per-rank manual slices and the (R, ...) SPMD
    stacks (where the leading-axis sum already makes it global)."""
    return jnp.stack([
        jnp.sum(jnp.square(u_val.astype(jnp.float32))),
        jnp.sum(jnp.square(acc.astype(jnp.float32))),
        jnp.sum(jnp.square(residual.astype(jnp.float32))),
    ])


def _pod_sparse_exchange(out, pod_axis: str, cap: int) -> jax.Array:
    """Cross-pod phase as a sparse stream exchange (DESIGN.md §7): the
    within-pod reduced (1, n) buffer is re-sparsified (its nnz is bounded
    by p_data * k, so ``cap`` loses nothing), every pod's (idx,val)
    stream is all-gathered, and the union scatter-adds back to dense.
    Exact — the same sum as the dense psum, at p_pod*cap items on the
    wire instead of the full n-vector. Native collectives only; the
    emulated lowering keeps the psum (identical numerics)."""
    from repro.core import sparse_stream as ss

    flat = out[0]
    stream = ss.from_mask(flat, flat != 0, cap)
    idx_all = jax.lax.all_gather(stream.idx, pod_axis)    # (p_pod, cap)
    val_all = jax.lax.all_gather(stream.val, pod_axis)
    dense = jnp.zeros_like(flat).at[idx_all.reshape(-1)].add(
        val_all.reshape(-1), mode="drop")                 # SENTINEL drops
    return dense[None]


def _reduce_flat_sparse(u_flat, algorithm: str, *,
                        coll: CollectiveContext, impl: str = "auto",
                        scatter: bool = False):
    """SSAR variants for flat (rows==1) buckets; returns (dense (n,),
    fold). ``fold`` is the capacity-clamped pre-scale mass of the
    portfolio algorithms (DESIGN.md §9) — the caller adds it into the
    bucket's EF residual (the global-residual rule) — and None for the
    unclamped classics.

    ``scatter`` (DESIGN.md §11) returns (my owned (n/p,) shard, fold)
    instead: the portfolio algorithms terminate at the shard natively
    (their final allgather never runs — the wire win); the classics have
    no reduce-scatter wire form, so they reduce replicated and slice —
    correct, no wire saving, and the cost model charges them the
    replicated rate (their registry entries are not scatter-capable)."""
    from repro.core import sparse_stream as ss
    from repro.core.allreduce import (
        ssar_balanced_split_inside,
        ssar_rearranged_rs_inside,
        ssar_recursive_double_inside,
        ssar_split_allgather_inside,
    )

    def _mine(dense):
        w = u_flat.n // coll.p
        return jax.lax.dynamic_slice_in_dim(
            dense.reshape(coll.p, w), coll.axis_rank(), 1, axis=0
        ).reshape(w)

    if algorithm == "ssar_recursive_double":
        out = ssar_recursive_double_inside(
            u_flat.to_stream(), axis_name=coll.axis_name, p=coll.p,
            n=u_flat.n)
        dense = out.to_dense(u_flat.n)
        return (_mine(dense) if scatter else dense), None
    if algorithm == "ssar_split_allgather":
        stream = ssar_split_allgather_inside(
            u_flat, axis_name=coll.axis_name, p=coll.p)
        dense = ss.densify(stream, u_flat.n)
        return (_mine(dense) if scatter else dense), None
    if algorithm == "ssar_balanced_split":
        return ssar_balanced_split_inside(
            u_flat, axis_name=coll.axis_name, p=coll.p, impl=impl,
            scatter=scatter)
    if algorithm == "ssar_rearranged_rs":
        return ssar_rearranged_rs_inside(
            u_flat, axis_name=coll.axis_name, p=coll.p, scatter=scatter)
    raise ValueError(f"not a flat sparse algorithm: {algorithm!r}")


def reduce_buckets(
    plan: SyncPlan,
    leaves: Sequence[jax.Array],
    residuals: dict,
    key: jax.Array,
    *,
    data_axis: str = "data",
    p_data: int,
    pod_axis: Optional[str] = None,
    p_pod: int = 1,
    native: bool = True,
    data_rank: Optional[jax.Array] = None,
    pod_rank: Optional[jax.Array] = None,
    telemetry: bool = True,
):
    """The REDUCE half of the bucket pipeline: pack -> EF add -> TopK ->
    per-bucket collective. Returns (reduced, new_residuals, telemetry)
    where ``reduced`` maps bucket name -> the fully reduced, scaled
    (rows, cols) f32 buffer (replicated over the dp axes once the
    collective is done) and ``telemetry`` maps each EF bucket's name ->
    the (4,) f32 [post-reduction nnz, wire bytes, mass coverage,
    EF-residual norm] stats vector (DESIGN.md §7, §10.5) — cheap
    in-graph counts the adaptive controller and health engine consume on
    the host (raw-dense buckets are not re-plannable and emit none).
    ``telemetry=False`` compiles the stats out entirely: the returned
    dict is empty and NO telemetry ops (including the mass psum) are
    traced — not merely DCE'd, absent from the jaxpr.

    Splitting here is what makes the non-blocking runtime possible
    (DESIGN.md §6): the pipelined superstep holds ``reduced`` in flight as
    TrainState.inflight for one step and applies it while the NEXT step's
    collectives run; :func:`apply_buckets` is the other half.

    Scattered plans (DESIGN.md §11) stop at the owner shard: every
    reduced value is my (1, rows, cols/p) owned column chunk (leading
    replica axis, like the residuals) instead of the replicated (rows,
    cols) buffer. Scatter-capable algorithms skip their final allgather
    (the wire win); raw-dense buckets lower to a true psum_scatter;
    non-capable algorithms and the emulated lowering reduce replicated
    and slice (exact parity, no wire saving). Clamp folds are self-local
    — each rank's fold covers only mass it clamped — so the EF residual
    update below is unchanged and residuals stay full width.

    leaves: flat per-rank grad leaves (original layouts, jax.tree.leaves
    order of the plan's param tree).
    residuals: bucket-keyed dict; inside shard_map each value carries its
    rank's slice with a leading replica axis of size 1.
    """
    from repro.core import topk as topk_mod
    from repro.core.allreduce import (
        dsar_split_allgather_batched_inside,
        safe_psum,
    )
    from repro.core.topk import UniformStream

    cfg = plan.cfg
    scattered = plan.scattered
    if scattered and p_pod > 1:
        raise ValueError(
            "scattered output mode is single-pod only (p_pod == 1): the "
            "owner shard of the cross-pod sum is not local to any pod")
    replicas = p_data * p_pod
    scale = 1.0 / replicas if cfg.mean else 1.0
    coll = CollectiveContext(data_axis, p_data, native=native, rank=data_rank)

    def _own_cols(out2d):
        """Replicated (rows, cols) -> my (rows, cols/p) column shard."""
        rows, cols = out2d.shape
        w = cols // p_data
        return jax.lax.dynamic_slice_in_dim(
            out2d.reshape(rows, p_data, w), coll.axis_rank(), 1, axis=1
        ).reshape(rows, w)

    def _psum_scatter_cols(x2d):
        """Dense reduce-scatter over columns: rank r keeps the summed
        columns [r*w, (r+1)*w) — the true (P-1)/P·n wire form natively;
        the psum-only lowering sums replicated and slices."""
        if native:
            return jax.lax.psum_scatter(
                x2d, data_axis, scatter_dimension=1, tiled=True)
        return _own_cols(coll.psum(x2d))

    if pod_axis is not None and pod_rank is None:
        if not native:
            raise ValueError("emulated multi-pod sync needs a pod rank feed")
        # Native callers (the per-leaf wrapper) may omit the feed; the
        # QSGD rounding key must still fold the pod rank so pods don't
        # share rounding bits.
        pod_rank = jax.lax.axis_index(pod_axis)

    reduced: dict = {}
    new_residuals: dict = {}
    telem: dict = {}
    bucket_idx = 0
    for group in plan.groups:
        buf = pack_group(group, leaves, cfg.bucket_size)     # (rows, cols) f32
        for b in group.buckets:
            seg = jax.lax.slice_in_dim(buf, b.col_start,
                                       b.col_start + b.cols, axis=1)
            if not b.sparse and b.name not in residuals:
                # Fused dense bucket: no feedback state, plain psum —
                # and no telemetry: nothing a replan could change here.
                # Scattered: the psum becomes a true reduce-scatter.
                if scattered:
                    out = _psum_scatter_cols(seg)
                    if pod_axis is not None:          # p_pod == 1 (guard)
                        out = safe_psum(out, pod_axis)
                    reduced[b.name] = (out * scale)[None]
                    bucket_idx += 1
                    continue
                out = safe_psum(seg, data_axis)
                if pod_axis is not None:
                    out = safe_psum(out, pod_axis)
                reduced[b.name] = out * scale
                bucket_idx += 1
                continue

            res = residuals[b.name][0]                        # strip replica axis
            with scopes.scope(scopes.COMPRESS):
                acc = res.astype(jnp.float32) + seg           # Alg. 2 line 1
                u, residual = topk_mod.compress2d(
                    acc, cfg.k_per_bucket, cfg.bucket_size)   # Alg. 2 line 2

            algorithm = b.algorithm
            # QSGD belongs to DSAR's dense gather phase ONLY: an SSAR
            # bucket rerouted to DSAR by the emulated fallback stays
            # unquantized, so every lowering of the same plan produces
            # the same values (the executor-parity invariant).
            qsgd = cfg.qsgd() if algorithm == "dsar_split_allgather" else None
            # A size-1 pod axis must not fold the (always-0) pod rank
            # into the rounding key: _qsgd_rand_all skips that fold, and
            # the two lowerings must draw identical bits (parity).
            qsgd_pod_rank = pod_rank if p_pod > 1 else None
            if not native and algorithm.startswith("ssar"):
                algorithm = "dsar_split_allgather"            # DESIGN.md §4
            fold = None
            if algorithm == "dense":
                # Residual-bearing bucket whose cost model picked a dense
                # end-representation (paper §5.3.3): STILL compress + EF,
                # then allreduce the densified stream — the legacy 'auto
                # -> dense' semantics of sparse_allreduce_inside.
                out = (_psum_scatter_cols(u.densify()) if scattered
                       else safe_psum(u.densify(), data_axis))
            elif algorithm == "dsar_split_allgather":
                rand = None
                if qsgd is not None:
                    rand = _qsgd_rand(key, bucket_idx, coll, qsgd_pod_rank,
                                      group.rows * b.cols // p_data, p_data)
                out = dsar_split_allgather_batched_inside(   # Alg. 2 line 3
                    u, axis_name=data_axis, p=p_data, qsgd=qsgd,
                    rand=rand, out_dtype=jnp.float32, impl=cfg.impl,
                    coll=coll, scatter=scattered)
            else:
                # SSAR keeps a sparse end-representation; flat rows only.
                assert group.rows == 1, (b.name, algorithm)
                flat = UniformStream(u.lidx[0], u.val[0], cfg.bucket_size)
                out, fold = _reduce_flat_sparse(flat, algorithm, coll=coll,
                                                impl=cfg.impl,
                                                scatter=scattered)
                out = out[None, :]
            if pod_axis is not None:
                if scattered:
                    out = safe_psum(out, pod_axis)  # p_pod == 1 (guard)
                elif b.pod_sparse and native and group.rows == 1:
                    # Adaptive cross-pod demotion (DESIGN.md §7): the
                    # within-pod result stayed under delta, so the DCN
                    # hop rides a sparse stream exchange, not dense psum.
                    cap = min(b.n, p_data * plan.bucket_k(group, b))
                    out = _pod_sparse_exchange(out, pod_axis, cap)
                else:
                    out = safe_psum(out, pod_axis)            # hierarchical
            reduced[b.name] = (out * scale)[None] if scattered else out * scale
            if fold is not None:
                # Global-residual rule (DESIGN.md §9): mass clamped off
                # the wire by a portfolio algorithm re-enters THIS rank's
                # EF residual at pre-scale magnitude, so it is
                # contributed exactly once on a later step — no gradient
                # mass is silently lost. Folded BEFORE the telemetry
                # read so the reported EF norm covers the clamped mass.
                with scopes.scope(scopes.COMPRESS):
                    residual = residual + fold[None, :]
            if telemetry:
                # Mass stats are per-rank sums here; ONE extra (3,) psum
                # per EF bucket makes them global (in-graph collective —
                # no host sync point, the no-added-sync invariant holds).
                m = coll.psum(_local_mass(u.val, acc, residual))
                if pod_axis is not None and p_pod > 1:
                    m = safe_psum(m, pod_axis)
                telem[b.name] = _bucket_telemetry(out, plan, group, b,
                                                  p_data, p_pod, coll=coll,
                                                  mass=m)
            new_residuals[b.name] = residual.astype(res.dtype)[None]
            bucket_idx += 1
    return reduced, new_residuals, telem


def apply_buckets(plan: SyncPlan, reduced: dict, leaves: Sequence[jax.Array]):
    """The APPLY half: reassemble each group buffer from its reduced
    buckets (name-keyed, as produced by :func:`reduce_buckets` — possibly
    a step earlier, via TrainState.inflight) and unpack back to the
    original leaf layouts. Pure reshapes/concats, no communication.

    leaves: shape/dtype references for the unpack (any per-rank leaf tree
    of the plan's layout). Returns the flat new-leaf list; leaves not
    covered by the plan come back as None.

    Scattered owner chunks are NOT unpackable here — the optimizer
    consumes them directly and the allgather moves to the PARAM side
    (train/train_step.py); the SPMD formulation may first rebuild full
    buffers via :func:`unchunk_buckets_spmd` and then apply. The shape
    check below catches the misuse before it becomes an opaque reshape.
    """
    for group in plan.groups:
        for b in group.buckets:
            if reduced[b.name].shape != (group.rows, b.cols):
                raise ValueError(
                    f"apply_buckets expects replicated (rows, cols) "
                    f"buffers; got {reduced[b.name].shape} for {b.name} — "
                    "scattered chunks feed the shard update "
                    "(_zero_scattered_update) or unchunk_buckets_spmd")
    new_leaves: list = [None] * plan.num_leaves
    for group in plan.groups:
        parts = [reduced[b.name] for b in group.buckets]
        out_buf = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        for leaf_id, arr in unpack_group(group, out_buf, leaves):
            new_leaves[leaf_id] = arr
    return new_leaves


def execute_plan(
    plan: SyncPlan,
    leaves: Sequence[jax.Array],
    residuals: dict,
    key: jax.Array,
    *,
    data_axis: str = "data",
    p_data: int,
    pod_axis: Optional[str] = None,
    p_pod: int = 1,
    native: bool = True,
    data_rank: Optional[jax.Array] = None,
    pod_rank: Optional[jax.Array] = None,
):
    """Synchronous sync of the planned leaves: :func:`reduce_buckets`
    composed immediately with :func:`apply_buckets` (the staleness=0
    path). Returns (new_leaves, new_residuals); telemetry is compiled
    out here — callers that want it compose the halves themselves."""
    reduced, new_residuals, _ = reduce_buckets(
        plan, leaves, residuals, key, data_axis=data_axis, p_data=p_data,
        pod_axis=pod_axis, p_pod=p_pod, native=native,
        data_rank=data_rank, pod_rank=pod_rank, telemetry=False)
    return apply_buckets(plan, reduced, leaves), new_residuals


# --------------------------------------------------------------------------
# Auto-SPMD formulation (no shard_map) — DESIGN.md §4.2
# --------------------------------------------------------------------------

def _qsgd_rand_all(key, bucket_idx: int, p_pod: int, p_data: int,
                   shard_elems: int):
    """(p_pod, p_data, shard) rounding bits — bit-compatible with the
    per-rank fold order of :func:`_qsgd_rand` (bucket, pod, data)."""
    with scopes.scope(scopes.QSGD):
        sub = jax.random.fold_in(key, bucket_idx)
        pods = []
        for a in range(p_pod):
            sp = jax.random.fold_in(sub, a) if p_pod > 1 else sub
            pods.append(jnp.stack([
                jax.random.bits(jax.random.fold_in(sp, j), (shard_elems,),
                                dtype=jnp.uint32)
                for j in range(p_data)
            ]))
        return jnp.stack(pods)


def reduce_buckets_spmd(
    plan: SyncPlan,
    leaves_r: Sequence[jax.Array],
    residuals: dict,
    key: jax.Array,
    *,
    p_data: int,
    p_pod: int = 1,
    telemetry: bool = True,
):
    """The same REDUCE half as :func:`reduce_buckets`, expressed as
    plain auto-SPMD array ops OUTSIDE any shard_map.

    Used on backends whose partitioner cannot lower a partial-manual
    training step at all (XLA-CPU container build: every explicit
    collective but psum, ``lax.scan`` bodies, and PartitionId abort — see
    DESIGN.md §4.2). The replica axis is a real leading axis instead:

    leaves_r: per-rank grads stacked as (R, *leaf_shape), R = p_pod*p_data,
    leading axis sharded over the dp mesh axes — "rank r's grads" IS the
    r-th slice, so per-rank TopK/EF semantics are preserved exactly and
    the reductions below lower to XLA's own all-reduces over the dp axes.
    residuals: bucket-keyed, FULL (R, rows, cols) arrays (not slices).

    Returns (reduced {bucket name -> (rows, cols) f32 buffer}, new
    bucket-keyed residuals (full arrays), telemetry {name -> (4,) f32
    [nnz, wire bytes, mass coverage, EF norm]; empty and fully compiled
    out under ``telemetry=False``). The mass sums need no collective
    here — the (R, ...) stacks already hold every rank's slice, so the
    all-axis sums ARE global. Numerics match the manual executor: sums over
    the leading axis are the allreduce; DSAR+QSGD replays every (pod,
    range-owner) quantization on the pod-local sums. SSAR algorithms
    reduce exactly (their wire layout has no numeric effect), so they
    fold into the same sum here — as does the sparse pod exchange of
    ``pod_sparse`` buckets (exact by construction). Telemetry still
    reports the wire cost of the NATIVE path this formulation models.

    Scattered plans (DESIGN.md §11): reduced values become the FULL
    (p_data, rows, cols/p) owner-chunk stack — chunk r holds exactly the
    columns rank r owns, bit-identical elements to the replicated
    buffer — laid out to shard 1/P per device under
    ``plan.scattered_specs``. XLA's partitioner turns the sum + chunked
    use into its own reduce-scatter; the formulation models the same
    wire the native scatter path pays.
    """
    from repro.comm.buckets import to_canonical
    from repro.core import topk as topk_mod

    cfg = plan.cfg
    scattered = plan.scattered
    if scattered and p_pod > 1:
        raise ValueError(
            "scattered output mode is single-pod only (p_pod == 1)")
    replicas = p_data * p_pod
    scale = 1.0 / replicas if cfg.mean else 1.0
    qsgd = cfg.qsgd()

    def _chunked(out2d):
        """(rows, cols) full sum -> (p_data, rows, cols/p) owner chunks."""
        rows, cols = out2d.shape
        w = cols // p_data
        return out2d.reshape(rows, p_data, w).transpose(1, 0, 2)

    reduced: dict = {}
    new_residuals: dict = {}
    telem: dict = {}
    bucket_idx = 0
    for group in plan.groups:
        segs = [
            jax.vmap(lambda g, s=slot: to_canonical(g, s.spec, cfg.bucket_size)
                     .astype(jnp.float32))(leaves_r[slot.leaf_id])
            for slot in group.slots
        ]
        buf = segs[0] if len(segs) == 1 else jnp.concatenate(segs, axis=2)
        pad = group.cols - buf.shape[2]
        if pad:
            buf = jnp.pad(buf, ((0, 0), (0, 0), (0, pad)))  # (R, rows, cols)
        for b in group.buckets:
            seg = jax.lax.slice_in_dim(buf, b.col_start,
                                       b.col_start + b.cols, axis=2)
            if not b.sparse and b.name not in residuals:
                # raw-dense: no telemetry (see _bucket_telemetry)
                out = seg.sum(axis=0) * scale
                reduced[b.name] = _chunked(out) if scattered else out
                bucket_idx += 1
                continue
            res = residuals[b.name]                           # (R, rows, cols)
            with scopes.scope(scopes.COMPRESS):
                acc = res.astype(jnp.float32) + seg
                u, residual = topk_mod.compress2d(
                    acc, cfg.k_per_bucket, cfg.bucket_size)
            dens = u.densify()                                # (R, rows, m*B)
            rows, mb = dens.shape[1], dens.shape[2]
            dpod = dens.reshape(p_pod, p_data, rows, mb).sum(axis=1)
            if qsgd is not None and b.algorithm == "dsar_split_allgather":
                shard = mb // p_data
                bq = qsgd.bucket_size
                nbq = shard // bq
                x = dpod.reshape(p_pod, rows, p_data, shard)
                x = x.transpose(0, 2, 1, 3)        # (p_pod, p_data, rows, shard)
                rand = _qsgd_rand_all(key, bucket_idx, p_pod, p_data,
                                      rows * shard)
                xq = _qsgd_roundtrip_spmd(
                    x.reshape(p_pod * p_data * rows * nbq, bq),
                    rand.reshape(p_pod * p_data * rows * nbq, bq),
                    qsgd, cfg.impl)
                dpod = (xq.reshape(p_pod, p_data, rows, shard)
                        .transpose(0, 2, 1, 3).reshape(p_pod, rows, mb))
            out = dpod.sum(axis=0)
            reduced[b.name] = (_chunked(out * scale) if scattered
                               else out * scale)
            if telemetry:
                telem[b.name] = _bucket_telemetry(
                    out, plan, group, b, p_data, p_pod,
                    mass=_local_mass(u.val, acc, residual))
            new_residuals[b.name] = residual.astype(res.dtype)
            bucket_idx += 1
    return reduced, new_residuals, telem


def unchunk_buckets_spmd(plan: SyncPlan, reduced: dict) -> dict:
    """Scattered (p, rows, w) owner-chunk stacks -> replicated (rows,
    cols) buffers. Pure reshapes: the SPMD formulation holds the full
    stack (chunk r IS columns [r*w, (r+1)*w)), so the inverse of the
    executor's ``_chunked`` is exact — XLA materializes the gather this
    implies, which is precisely the param/grad allgather the manual
    scattered path issues explicitly."""
    out = dict(reduced)
    for group in plan.groups:
        for b in group.buckets:
            ch = reduced[b.name]
            p, rows, w = ch.shape
            out[b.name] = ch.transpose(1, 0, 2).reshape(rows, p * w)
    return out


def apply_buckets_spmd(plan: SyncPlan, reduced: dict,
                       leaves_r: Sequence[jax.Array]):
    """APPLY half of the auto-SPMD formulation: unpack name-keyed reduced
    buffers back to original leaf layouts (replica-replicated). leaves_r
    carry the (R, *leaf) per-rank layout; rank-0 slices stand in as the
    shape/dtype references for the unpack."""
    ref_leaves = [l[0] for l in leaves_r]
    return apply_buckets(plan, reduced, ref_leaves)


def execute_plan_spmd(
    plan: SyncPlan,
    leaves_r: Sequence[jax.Array],
    residuals: dict,
    key: jax.Array,
    *,
    p_data: int,
    p_pod: int = 1,
):
    """Synchronous auto-SPMD sync: :func:`reduce_buckets_spmd` composed
    immediately with :func:`apply_buckets_spmd` (the staleness=0 path).
    Returns (synced leaves in original layout, new residuals); telemetry
    is compiled out, as in :func:`execute_plan`."""
    reduced, new_residuals, _ = reduce_buckets_spmd(
        plan, leaves_r, residuals, key, p_data=p_data, p_pod=p_pod,
        telemetry=False)
    return apply_buckets_spmd(plan, reduced, leaves_r), new_residuals


def _qsgd_roundtrip_spmd(x2d, rand2d, qsgd, impl: str):
    from repro.core.allreduce import _qsgd_roundtrip

    return _qsgd_roundtrip(x2d, rand2d, qsgd, impl, jnp.float32)


# --------------------------------------------------------------------------
# Serve-time activation exchange (DESIGN.md §8)
# --------------------------------------------------------------------------
#
# The decode-time MoE combine is an allreduce of a (T, d) buffer over the
# expert/model axis whose per-shard partial is ROW-sparse: token row t is
# nonzero only when token t is active AND routed one of its experts to
# this shard. The ServePlan (comm/plan.py) picks the wire representation
# per compiled decode step; these two functions are its executor.
#
# Exactness contract (the serve analogue of the pod_sparse exchange): the
# stream path computes THE SAME SUM as the dense psum, bit for bit, as
# long as every shard's nonzero row count stays under the stream capacity
# — which the engine's occupancy guard enforces before dispatching a
# sparse-plan step.


def _row_stream_roundtrip(partial: jax.Array, cap: int) -> jax.Array:
    """(T, d) partial -> row stream at capacity ``cap`` -> dense again.
    Identity (bit-for-bit) while nonzero rows <= cap; materializing the
    round-trip in-graph is what makes the emulated/SPMD lowerings of the
    stream path numerically IDENTICAL to the dense reference — and makes
    a capacity overflow visible as a parity break instead of silence."""
    from repro.core import sparse_stream as ss

    mask = jnp.any(partial != 0, axis=1)
    return ss.densify_rows(ss.from_row_mask(partial, mask, cap),
                           partial.shape[0])


def exchange_activation(
    partial: jax.Array,
    algorithm: str,
    *,
    coll: CollectiveContext,
):
    """One shard's (T, d) combine partial -> the fully-summed (T, d),
    INSIDE a shard_map manual over the expert/model axis.

    'dense': the reference psum. 'stream_gather@C': the planned (idx,val)
    row-stream exchange — native lowerings all-gather each rank's stream
    and scatter every foreign stream back to dense before the sum;
    emulated (psum-only) lowerings round-trip the partial through the
    stream locally and ride the psum wire, exactly like the pod_sparse
    demotion (DESIGN.md §7.2): modeled stream wire, identical numerics.
    """
    from repro.core import sparse_stream as ss

    if algorithm == "dense":
        return coll.psum(partial)
    from repro.core.cost_model import parse_stream_cap

    cap = parse_stream_cap(algorithm)
    if not coll.native:
        return coll.psum(_row_stream_roundtrip(partial, cap))
    t = partial.shape[0]
    stream = ss.from_row_mask(partial, jnp.any(partial != 0, axis=1), cap)
    idx_all = coll.all_gather(stream.idx[None], axis=0)     # (p, cap)
    val_all = coll.all_gather(stream.val[None], axis=0)     # (p, cap, d)
    dense_all = jax.vmap(
        lambda i, v: ss.densify_rows(
            ss.RowStream(i, v, jnp.asarray(0, jnp.int32)), t)
    )(idx_all, val_all)                                     # (p, T, d)
    return dense_all.sum(axis=0)


def exchange_activation_spmd(partials: jax.Array, algorithm: str):
    """The auto-SPMD formulation of :func:`exchange_activation`: the
    shard axis is a real leading axis (p, T, d) — shard s's partial IS
    the s-th slice — and the sum over it lowers to XLA's own all-reduce
    over the sharded axis (DESIGN.md §4.2). The stream path round-trips
    each shard's partial through its row stream first: bitwise the same
    summands as the dense path while under capacity, so sparse == dense
    exactly, whatever reduction order the backend picks."""
    from repro.core.cost_model import parse_stream_cap

    if algorithm != "dense":
        cap = parse_stream_cap(algorithm)
        partials = jax.vmap(lambda x: _row_stream_roundtrip(x, cap))(partials)
    return partials.sum(axis=0)
