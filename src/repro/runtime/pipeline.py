"""Pipelined stale-gradient supersteps (DESIGN.md §6).

The synchronous sparcml step serializes compute and communication:

    grads_t -> reduce(grads_t) -> apply -> update      (blocks on the wire)

The pipelined step splits the executor into its compose-able halves
(``comm.reduce_buckets`` / ``comm.apply_buckets``) and staggers them by
``staleness`` steps (bounded at 1):

    step t:  grads_t = backward(params_t, batch_t)
             params_{t+1} = update(params_t, apply(inflight))   # = R(g_{t-1})
             inflight' = reduce_buckets(grads_t)                # in flight
                                                                # until t+1

so the collectives of step t-1 drain while step t's forward/backward
runs — on hardware with async collectives the scheduler overlaps them;
on the host driver the removed per-step dependency is what lets dispatch
run ahead. Error-feedback residuals stay keyed by bucket and are updated
by the REDUCE half every step, exactly as in the synchronous executor.

``staleness=0`` degenerates to the synchronous composition (execute_plan)
with no in-flight state — the same ops in the same order, so its output
matches the synchronous step bit-for-bit.

In-flight buffers carry a scalar validity flag (``VALID_KEY``): steps
that would apply INVALID (all-zero) buffers — the first step, and the
first step after every attach/resume/restore — run at lr 0, so
parameters are untouched until a real reduction lands (the optimizer's
count still advances and its moments decay once — a one-step offset,
negligible and documented).

Three lowerings (DESIGN.md §4): ``manual`` (shard_map + native
collectives, ``train_step``'s and the default), ``emulated`` (shard_map +
psum-emulated collectives), ``spmd`` (auto-SPMD, no shard_map); tests
force the last two. ``build_superstep``
wraps the step in a jitted ``lax.scan`` over K steps, so one dispatch
covers a whole superstep and the per-step jaxpr keeps O(num_buckets)
collectives.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import comm, compat
from repro.models.model import Model
from repro.optim.optimizers import clip_by_global_norm, opt_update
from repro.optim.schedule import make_schedule
from repro.runtime.faults import FAULT_KEY
from repro.train import train_step as ts
from repro.train.state import TrainConfig, TrainState

LOWERINGS = ("manual", "emulated", "spmd")

# Scalar validity flag carried inside the in-flight dict (f32 0/1): zero
# in-flight buffers (fresh start, resume, post-restore attach) must be
# applied at lr 0 REGARDLESS of the step counter — gating on step alone
# would apply a zero gradient at full lr after every resume. Bucket names
# are "g<gid>b<idx>", so the key cannot collide.
VALID_KEY = "__valid__"


def resolve_lowering(lowering: Optional[str] = None) -> str:
    """Default to build_train_step's lowering (manual, native
    collectives); tests force the others to assert cross-lowering
    parity."""
    if lowering is None:
        return "manual"
    if lowering not in LOWERINGS:
        raise ValueError(f"lowering must be one of {LOWERINGS}: {lowering!r}")
    return lowering


def pipelined_state_shapes(model: Model, tcfg: TrainConfig, mesh: Mesh, *,
                           staleness: int = 1, plan=None):
    """(abstract TrainState, spec TrainState, SyncPlan) for the pipelined
    step: the synchronous state plus — when staleness > 0 — the in-flight
    reduced-bucket buffers (``TrainState.inflight``, keyed like residuals
    by bucket name).

    ``plan`` substitutes a REPLANNED SyncPlan (DESIGN.md §7) for the
    freshly derived one. Replans are layout-invariant by construction
    (``BucketSpec.ef`` pins the residual set), so the returned shapes are
    identical for every version of one base plan — asserted here."""
    if tcfg.sync.mode != "sparcml":
        raise ValueError(
            "the pipelined runtime overlaps the planned sparse collectives "
            "and requires sync.mode='sparcml' (dense mode has no explicit "
            "reduce to defer — XLA owns its collectives)")
    if staleness not in (0, 1):
        raise ValueError(f"staleness is bounded at 1, got {staleness}")
    shapes, specs, built = ts.state_shapes(model, tcfg, mesh,
                                           return_plan=True)
    if plan is None:
        plan = built
    elif (plan.residual_shapes() != built.residual_shapes()
          or plan.inflight_shapes() != built.inflight_shapes()):
        # full name->shape dicts, not just key sets: a plan from another
        # (dp, bucket-size) configuration can reuse the generic g<i>b<j>
        # names and would otherwise die later inside jit with an opaque
        # XLA shape error instead of this one
        raise ValueError(
            "plan override changes the residual/in-flight layout — replans "
            "must come from SyncPlan.replan() of this configuration's base "
            "plan")
    if staleness:
        shapes = shapes._replace(inflight={
            **plan.inflight_shapes(),
            VALID_KEY: jax.ShapeDtypeStruct((), jnp.float32)})
        specs = specs._replace(inflight={
            **plan.inflight_specs(ts.dp_axes_of(mesh)),
            VALID_KEY: P()})
    return shapes, specs, plan


def attach_inflight(state: TrainState, plan, mesh: Mesh) -> TrainState:
    """Zero in-flight buffers onto a synchronous-shaped TrainState (resume
    from a checkpoint, or hand-off from Trainer.run): the validity flag
    starts at 0, so the first pipelined step applies a zero gradient at
    lr 0 (the optimizer moments still decay once) — whatever the step."""
    if state.inflight is not None:
        return state
    shapes = plan.inflight_shapes()
    specs = plan.inflight_specs(ts.dp_axes_of(mesh))
    zeros = {
        k: jax.device_put(jnp.zeros(s.shape, s.dtype),
                          NamedSharding(mesh, specs[k]))
        for k, s in shapes.items()
    }
    zeros[VALID_KEY] = jax.device_put(jnp.zeros((), jnp.float32),
                                      NamedSharding(mesh, P()))
    return state._replace(inflight=zeros)


# --------------------------------------------------------------------------
# Step-body construction (shared by single-step and superstep builders)
# --------------------------------------------------------------------------

def _pipelined_batch_specs(cfg, mesh: Mesh, inject: bool) -> dict:
    """Batch specs for the pipelined builders: the data fields plus —
    when the chaos harness is riding along — the replicated per-grad-leaf
    injection vector (``faults.FAULT_KEY``, (n_leaves,) f32)."""
    b = ts.batch_specs(cfg, mesh)
    if inject:
        b = {**b, FAULT_KEY: P()}
    return b


def _make_raw_step(model: Model, tcfg: TrainConfig, mesh: Mesh,
                   staleness: int, lowering: Optional[str],
                   plan=None, telemetry: bool = True,
                   guard: bool = False, inject: bool = False):
    """Un-jitted pipelined step (state, batch, key) -> (state, metrics),
    plus (shapes, specs, plan). The body mirrors build_train_step's
    sparcml branches with the sync split at the staleness boundary —
    kept as a twin on purpose (folding them would put the runtime on the
    synchronous hot path); tests/test_runtime.py compares the two
    implementations output-for-output on every lowering, so any silent
    divergence between the twins fails CI.

    ``plan`` runs a replanned SyncPlan (adaptive runtime, DESIGN.md §7)
    instead of the derived base plan. ``telemetry=False`` drops the
    per-bucket stats from the metrics dict AND from the traced graph:
    the flag is threaded into the executor so the nnz/wire/mass counts
    (and the mass psum) are never emitted, not merely DCE'd — asserted
    at the jaxpr level in tests (the overhead A/B in
    benchmarks/bench_adapt.py and bench_obs_health.py).

    ``guard=True`` adds the in-graph all-finite check over the RAW grad
    leaves (DESIGN.md §12.2): a non-finite gradient anywhere makes the
    step a no-op on params, optimizer state, EF residuals AND in-flight
    buffers (the step counter still advances), and ``metrics["nonfinite"]``
    reports 1.0 for the tripped step. ``inject=True`` additionally
    consumes a ``faults.FAULT_KEY`` leaf from the batch dict — the chaos
    harness's per-grad-leaf NaN/Inf vector, applied by pure select before
    the reduce half, so an all-zero vector is bit-exact with no injector."""
    cfg = model.cfg
    sched = make_schedule(tcfg.schedule)
    lowering = resolve_lowering(lowering)
    shapes, specs, plan = pipelined_state_shapes(model, tcfg, mesh,
                                                 staleness=staleness,
                                                 plan=plan)
    pspecs = specs.params
    dp_ax = ts.dp_axes_of(mesh)
    dp_total = ts.dp_total_of(mesh)
    n_micro = tcfg.microbatches
    data_axis = dp_ax[-1]
    p_data = mesh.shape[data_axis]
    pod_axis = dp_ax[0] if len(dp_ax) > 1 else None
    p_pod = mesh.shape[pod_axis] if pod_axis else 1
    grad_clip = tcfg.optimizer.grad_clip
    # Scattered plans (DESIGN.md §11): the in-flight buffers are owner
    # CHUNKS; the apply half is the shard update itself (no grad-side
    # allgather ever runs) and the dense param allgather it issues sits
    # at the tail of step t's graph next to the reduce — independent of
    # it — so both drain while step t+1's forward runs ahead.
    scattered = plan.scattered

    def _guard_state(fin, new_state, old_state):
        """Roll every stateful component back to its pre-step value on a
        guard trip (fin 0.0); the step counter still advances so the
        schedule/data replay stay aligned. Keeping the OLD in-flight
        buffers means the previous step's (clean) reduction is re-applied
        on the next clean step — nothing is lost but the poisoned grads.
        The old VALID_KEY rides along unchanged."""
        if fin is None:
            return new_state
        return TrainState(
            ts.guard_select(fin, new_state.params, old_state.params),
            ts.guard_select(fin, new_state.opt, old_state.opt),
            ts.guard_select(fin, new_state.residuals, old_state.residuals),
            new_state.step,
            None if new_state.inflight is None else ts.guard_select(
                fin, new_state.inflight, old_state.inflight))

    def _finish(state, applied, loss, lr, new_res, new_inflight, telem, *,
                zero1_update, fin=None):
        """Clip + optimizer update + state assembly (lowering-agnostic).
        zero1_update: callable(params, grads, opt, lr) for this lowering.
        fin: guard verdict (f32 1/0) or None when the guard is off."""
        applied, gnorm = clip_by_global_norm(applied, grad_clip)
        # Gate applies of INVALID (all-zero) in-flight buffers — first
        # step, and first step after every attach/resume — to lr 0.
        lr_eff = lr if staleness == 0 else lr * state.inflight[VALID_KEY]
        if tcfg.zero1:
            new_p, new_opt = zero1_update(state.params, applied, state.opt,
                                          lr_eff)
        else:
            new_p, new_opt = opt_update(state.params, applied, state.opt,
                                        lr_eff, tcfg.optimizer)
        new_state = TrainState(new_p, new_opt, new_res, state.step + 1,
                               new_inflight)
        new_state = _guard_state(fin, new_state, state)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr_eff}
        if fin is not None:
            metrics["nonfinite"] = 1.0 - fin
        if telemetry:
            metrics["telemetry"] = telem
        return new_state, metrics

    if lowering == "spmd":
        # ----- auto-SPMD: replica axis is a real leading axis (§4.2) -----
        def raw_step(state: TrainState, batch, key):
            lr = sched(state.step)
            batch = dict(batch)
            fault_vec = batch.pop(FAULT_KEY) if inject else None

            def split_ranks(x):
                out = x.reshape((dp_total, x.shape[0] // dp_total)
                                + x.shape[1:])
                spec = P(tuple(dp_ax), *([None] * (out.ndim - 1)))
                return jax.lax.with_sharding_constraint(
                    out, NamedSharding(mesh, spec))

            batch_r = jax.tree.map(split_ranks, batch)
            loss_r, grads_r = jax.vmap(
                lambda b: ts._accumulated_grads(model, state.params, b,
                                                n_micro))(batch_r)
            loss = jnp.mean(loss_r)
            leaves_r, gtree = jax.tree.flatten(grads_r)
            leaves_spec = gtree.flatten_up_to(pspecs)
            leaves_r = [
                jax.lax.with_sharding_constraint(
                    g, NamedSharding(mesh, P(tuple(dp_ax),
                                             *(s if s is not None else ()))))
                for g, s in zip(leaves_r, leaves_spec)
            ]
            if fault_vec is not None:
                leaves_r = ts.inject_nonfinite_leaves(leaves_r, fault_vec)
            # Guard verdict on the raw (post-injection) grads: the leaves
            # here are full global arrays, so the check covers every rank.
            fin = ts.all_finite_leaves(leaves_r) if guard else None
            if staleness == 0:
                # execute_plan_spmd minus the telemetry drop: same ops,
                # same order (the staleness=0 == synchronous invariant).
                reduced, new_res, telem = comm.reduce_buckets_spmd(
                    plan, leaves_r, state.residuals, key,
                    p_data=p_data, p_pod=p_pod, telemetry=telemetry)
                chunks = reduced
                new_inflight = None
            else:
                chunks = state.inflight
                new_inflight, new_res, telem = comm.reduce_buckets_spmd(
                    plan, leaves_r, state.residuals, key,
                    p_data=p_data, p_pod=p_pod, telemetry=telemetry)
                new_inflight[VALID_KEY] = jnp.ones((), jnp.float32)
            if scattered:
                applied_leaves = comm.apply_buckets_spmd(
                    plan, comm.unchunk_buckets_spmd(plan, chunks), leaves_r)
                applied = gtree.unflatten(applied_leaves)
                applied, gnorm = clip_by_global_norm(applied, grad_clip)
                lr_eff = (lr if staleness == 0
                          else lr * state.inflight[VALID_KEY])
                new_p, new_opt = ts._zero_scattered_update_spmd(
                    state.params, applied, state.opt, lr_eff, tcfg, plan)
                new_state = TrainState(new_p, new_opt, new_res,
                                       state.step + 1, new_inflight)
                new_state = _guard_state(fin, new_state, state)
                metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr_eff}
                if fin is not None:
                    metrics["nonfinite"] = 1.0 - fin
                if telemetry:
                    metrics["telemetry"] = telem
                return new_state, metrics
            applied_leaves = comm.apply_buckets_spmd(plan, chunks, leaves_r)
            applied = gtree.unflatten(applied_leaves)
            return _finish(
                state, applied, loss, lr, new_res, new_inflight, telem,
                zero1_update=lambda p, g, o, l: ts._zero1_update_spmd(
                    p, g, o, l, tcfg, pspecs, dp_total), fin=fin)

        return raw_step, shapes, specs, plan

    # ----- manual dp (shard_map), native or psum-emulated collectives -----
    native = lowering == "manual"

    def inner(state: TrainState, batch, key, rid):
        lr = sched(state.step)
        batch = dict(batch)
        fault_vec = batch.pop(FAULT_KEY) if inject else None
        loss, grads = ts._accumulated_grads(model, state.params, batch,
                                            n_micro)
        loss = jax.lax.pmean(loss, dp_ax[-1])
        if len(dp_ax) > 1:
            loss = jax.lax.pmean(loss, dp_ax[0])
        dp_index = rid[0]
        data_rank = dp_index % p_data
        pod_rank = dp_index // p_data if pod_axis else None
        leaves_g, gtree = jax.tree.flatten(grads)
        if fault_vec is not None:
            leaves_g = ts.inject_nonfinite_leaves(leaves_g, fault_vec)
        if guard:
            # Local verdict, then the cross-rank AND via pmin — a plain
            # lax reduction, so it lowers under both the native and the
            # psum-emulated collective paths (same as the loss pmean).
            fin = ts.all_finite_leaves(leaves_g)
            fin = jax.lax.pmin(fin, dp_ax[-1])
            if len(dp_ax) > 1:
                fin = jax.lax.pmin(fin, dp_ax[0])
        else:
            fin = None
        coll_kwargs = dict(
            data_axis=data_axis, p_data=p_data, pod_axis=pod_axis,
            p_pod=p_pod, native=native, data_rank=data_rank,
            pod_rank=pod_rank, telemetry=telemetry)
        if scattered:
            if staleness == 0:
                reduced, new_res, telem = comm.reduce_buckets(
                    plan, leaves_g, state.residuals, key, **coll_kwargs)
                chunks = reduced
                new_inflight = None
            else:
                chunks = state.inflight
                new_inflight, new_res, telem = comm.reduce_buckets(
                    plan, leaves_g, state.residuals, key, **coll_kwargs)
                new_inflight[VALID_KEY] = jnp.ones((), jnp.float32)
            lr_eff = (lr if staleness == 0
                      else lr * state.inflight[VALID_KEY])
            coll = comm.CollectiveContext(data_axis, p_data, native=native,
                                          rank=data_rank)
            new_p, new_opt, gnorm = ts._zero_scattered_update(
                state.params, chunks, state.opt, lr_eff, tcfg, plan, coll)
            new_state = TrainState(new_p, new_opt, new_res, state.step + 1,
                                   new_inflight)
            new_state = _guard_state(fin, new_state, state)
            metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr_eff}
            if fin is not None:
                metrics["nonfinite"] = 1.0 - fin
            if telemetry:
                metrics["telemetry"] = telem
            return new_state, metrics
        if staleness == 0:
            # execute_plan minus the telemetry drop (same ops, same order).
            reduced, new_res, telem = comm.reduce_buckets(
                plan, leaves_g, state.residuals, key, **coll_kwargs)
            applied_leaves = comm.apply_buckets(plan, reduced, leaves_g)
            new_inflight = None
        else:
            applied_leaves = comm.apply_buckets(plan, state.inflight,
                                                leaves_g)
            new_inflight, new_res, telem = comm.reduce_buckets(
                plan, leaves_g, state.residuals, key, **coll_kwargs)
            new_inflight[VALID_KEY] = jnp.ones((), jnp.float32)
        applied = gtree.unflatten(applied_leaves)

        def zero1_update(params, grads_, opt, lr_):
            gather_ctxs = [
                comm.CollectiveContext(ax, mesh.shape[ax], native=native,
                                       rank=(pod_rank if ax == pod_axis
                                             else data_rank))
                for ax in dp_ax
            ]
            return ts._zero1_update(params, grads_, opt, lr_, tcfg, pspecs,
                                    dp_ax, dp_index, dp_total, gather_ctxs)

        return _finish(state, applied, loss, lr, new_res, new_inflight,
                       telem, zero1_update=zero1_update, fin=fin)

    in_state_specs = ts.manual_only_tree(specs)
    in_batch_specs = ts.manual_only_tree(
        _pipelined_batch_specs(cfg, mesh, inject))
    rid_spec = P(tuple(dp_ax))
    mapped = compat.shard_map(
        inner, mesh=mesh,
        in_specs=(in_state_specs, in_batch_specs, P(), rid_spec),
        out_specs=(in_state_specs, P()),
        check_vma=False,
        axis_names=set(dp_ax),
    )

    def raw_step(state: TrainState, batch, key):
        # rank-id feed: each rank's slice of arange(dp_total) — the
        # emulated collectives cannot lower axis_index (DESIGN.md §4).
        rid = jnp.arange(dp_total, dtype=jnp.int32)
        return mapped(state, batch, key, rid)

    return raw_step, shapes, specs, plan


# --------------------------------------------------------------------------
# Public builders
# --------------------------------------------------------------------------

def build_pipelined_step(model: Model, tcfg: TrainConfig, mesh: Mesh, *,
                         staleness: int = 1, lowering: Optional[str] = None,
                         donate: bool = True, plan=None,
                         telemetry: bool = True, guard: bool = False,
                         inject: bool = False):
    """Single pipelined step, jitted. Returns
    (step_fn(state, batch, key) -> (state, metrics), (shapes, specs), plan).
    ``plan``/``telemetry``/``guard``/``inject``: see :func:`_make_raw_step`.
    """
    raw_step, shapes, specs, plan = _make_raw_step(model, tcfg, mesh,
                                                   staleness, lowering,
                                                   plan, telemetry,
                                                   guard, inject)
    bspecs = _pipelined_batch_specs(model.cfg, mesh, inject)
    sh = lambda t: ts.shardings_tree(mesh, t)
    jitted = jax.jit(
        raw_step,
        in_shardings=(sh(specs), sh(bspecs), NamedSharding(mesh, P())),
        out_shardings=(sh(specs), NamedSharding(mesh, P())),
        donate_argnums=(0,) if donate else (),
    )
    return jitted, (shapes, specs), plan


def build_superstep(model: Model, tcfg: TrainConfig, mesh: Mesh, *,
                    staleness: int = 1, steps: int = 4,
                    lowering: Optional[str] = None, donate: bool = True,
                    unroll: bool = False, plan=None,
                    telemetry: bool = True, guard: bool = False,
                    inject: bool = False):
    """K-step superstep: one jitted K-step loop over the pipelined step.
    Returns (superstep_fn, (shapes, specs), plan) where
    ``superstep_fn(state, batches, keys) -> (state, metrics)`` takes
    per-leaf batches stacked on a leading (steps,) axis and keys stacked
    as (steps, 2), and returns metrics stacked the same way.

    One dispatch covers K training steps, so the host syncs (and pays the
    per-call dispatch cost — substantial for multi-device programs) once
    per superstep instead of once per step. ``unroll=False`` uses
    ``lax.scan`` (body traced once: compile time and per-step collective
    count are O(1) in K, but XLA may copy loop carries per iteration);
    ``unroll=True`` lays the K steps out straight-line (carries alias
    freely — faster on backends with expensive loop carries, e.g. the
    emulated-CPU host — at K-times the trace/compile cost).
    """
    if steps < 1:
        raise ValueError(f"superstep needs steps >= 1, got {steps}")
    raw_step, shapes, specs, plan = _make_raw_step(model, tcfg, mesh,
                                                   staleness, lowering,
                                                   plan, telemetry,
                                                   guard, inject)
    bspecs = _pipelined_batch_specs(model.cfg, mesh, inject)
    stacked_bspecs = jax.tree.map(lambda s: P(None, *s), bspecs,
                                  is_leaf=lambda x: isinstance(x, P))
    sh = lambda t: ts.shardings_tree(mesh, t)

    if unroll:
        def superstep(state: TrainState, batches, keys):
            n = jax.tree.leaves(batches)[0].shape[0]
            ms = []
            for i in range(n):
                b = jax.tree.map(lambda x: x[i], batches)
                state, m = raw_step(state, b, keys[i])
                ms.append(m)
            return state, jax.tree.map(lambda *xs: jnp.stack(xs), *ms)
    else:
        def superstep(state: TrainState, batches, keys):
            def body(carry, bk):
                b, k = bk
                return raw_step(carry, b, k)

            return jax.lax.scan(body, state, (batches, keys))

    jitted = jax.jit(
        superstep,
        in_shardings=(sh(specs), sh(stacked_bspecs), NamedSharding(mesh, P())),
        out_shardings=(sh(specs), NamedSharding(mesh, P())),
        donate_argnums=(0,) if donate else (),
    )
    return jitted, (shapes, specs), plan
