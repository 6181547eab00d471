"""train_step builder: dense (XLA-auto collectives) and sparcml (paper
Alg. 2) gradient synchronization, microbatch accumulation, ZeRO-1 sharded
optimizer state, all under one jitted function per configuration.

sparcml mode structure (DESIGN.md §2.2):

  shard_map over dp axes ('pod','data'), AUTO over 'model':
    local grads (jax.grad on the rank's batch shard; TP collectives are
    inserted by XLA under the auto axis)
    -> accumulate over microbatches locally (ONE sync per step — the
       paper's non-blocking/fusion insight, free here by construction)
    -> sync_grads_inside: bucket-TopK + error feedback + sparse allreduce
       (+ optional QSGD on the dense phase) over 'data', psum over 'pod'
    -> ZeRO-1 update: each rank updates a 1/dp slice of the canonical
       param layout from its optimizer-state chunk, then all-gathers
       updated slices (composes with DSAR exactly like the paper's dense
       allgather second phase).

dense mode: plain jit; params/opt-state optionally FSDP-sharded (ZeRO-3);
XLA inserts reduce-scatter/all-gather from shardings.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import comm, compat
from repro.core import compressor as comp
from repro.models.config import ModelConfig
from repro.models.model import Model
from repro.models.specs import param_specs
from repro.obs import scopes
from repro.optim.optimizers import clip_by_global_norm, init_opt_state, opt_update
from repro.optim.schedule import make_schedule
from repro.train.state import TrainConfig, TrainState


def dp_axes_of(mesh: Mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def dp_total_of(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in dp_axes_of(mesh)]))


def _only_dp(s) -> bool:
    names = s if isinstance(s, tuple) else (s,)
    return all(n in ("pod", "data") for n in names if n) and any(names)


def manual_only(spec):
    """shard_map in_specs may reference only MANUAL (dp) axes; the 'model'
    sharding of params/opt rides along under auto."""
    if spec is None:
        return None
    return P(*[(s if _only_dp(s) else None) for s in spec])


def manual_only_tree(specs):
    return jax.tree.map(
        manual_only, specs, is_leaf=lambda x: x is None or isinstance(x, P))


def shardings_tree(mesh: Mesh, specs):
    """PartitionSpec tree (None = replicated) -> NamedSharding tree."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s if s is not None else P()), specs,
        is_leaf=lambda x: x is None or isinstance(x, P))


# --------------------------------------------------------------------------
# ZeRO-1 canonical chunking (sparcml mode)
# --------------------------------------------------------------------------

def _chunk_cols(shape, spec, cfg_sync, dp_total: int) -> tuple[int, int]:
    rows, cols = comp.canonical_shape(shape, spec, cfg_sync.bucket_size)
    assert cols % dp_total == 0, (shape, cols, dp_total)
    return rows, cols // dp_total


def zero1_state_shapes(param_shapes, specs, tcfg: TrainConfig, dp_total: int):
    """Opt-state leaves stored as (dp_total, rows, cols/dp) canonical chunks."""
    n_slots = 2 if tcfg.optimizer.kind == "adamw" else 1

    def one(sd, spec):
        rows, w = _chunk_cols(sd.shape, spec, tcfg.sync, dp_total)
        return jax.ShapeDtypeStruct((dp_total, rows, w), tcfg.optimizer.state_dtype)

    mu = jax.tree.map(one, param_shapes, specs)
    out = {"mu": mu, "count": jax.ShapeDtypeStruct((), jnp.int32)}
    if n_slots == 2:
        out["nu"] = jax.tree.map(one, param_shapes, specs)
    return out


def zero1_state_specs(param_shapes, specs, tcfg: TrainConfig, dp_axes):
    def one(sd, spec):
        ax = comp._model_axis(spec)
        return P(dp_axes, "model" if ax is not None else None, None)

    mu = jax.tree.map(one, param_shapes, specs)
    out = {"mu": mu, "count": P()}
    if tcfg.optimizer.kind == "adamw":
        out["nu"] = mu
    return out


# --------------------------------------------------------------------------
# ZeRO scattered chunking (sparcml + output_mode='scattered', DESIGN.md §11)
# --------------------------------------------------------------------------

def zero_scattered_state_shapes(plan, tcfg: TrainConfig):
    """Optimizer moments partitioned by the plan's OWNED RANGES: one
    (dp_total, rows, cols/dp) chunk per fusion BUCKET (keyed like the
    residuals, by bucket name) instead of per leaf — the same ranges the
    scattered reduce terminates at, so the update never reshuffles the
    exchange output. Every bucket carries moments (raw-dense buckets
    still own their params' update)."""

    def chunks():
        return {
            b.name: jax.ShapeDtypeStruct(
                (plan.dp_total, g.rows, plan.owned_cols(b)),
                tcfg.optimizer.state_dtype)
            for g in plan.groups for b in g.buckets
        }

    out = {"mu": chunks(), "count": jax.ShapeDtypeStruct((), jnp.int32)}
    if tcfg.optimizer.kind == "adamw":
        out["nu"] = chunks()
    return out


def zero_scattered_state_specs(plan, tcfg: TrainConfig, dp_axes):
    sp = plan.scattered_specs(dp_axes)
    out = {"mu": dict(sp), "count": P()}
    if tcfg.optimizer.kind == "adamw":
        out["nu"] = dict(sp)
    return out


# --------------------------------------------------------------------------
# State construction
# --------------------------------------------------------------------------

def state_shapes(model: Model, tcfg: TrainConfig, mesh: Mesh, key=None,
                 return_plan: bool = False):
    """(abstract TrainState, TrainState of PartitionSpecs) without
    allocating. return_plan=True additionally returns the SyncPlan whose
    bucket names key the residual dict (None outside sparcml mode) — the
    ONE plan both the state layout and the step executor must share."""
    if key is None:
        key = jax.random.PRNGKey(tcfg.seed)
    pshapes = jax.eval_shape(model.init, key)
    fsdp_axes = dp_axes_of(mesh) if tcfg.fsdp else None
    pspecs = param_specs(pshapes, model.cfg, fsdp_axes)
    dp_total = dp_total_of(mesh)
    dp_ax = dp_axes_of(mesh)

    plan = None
    if tcfg.sync.mode == "sparcml":
        # Fusion plan (DESIGN.md §3): residual state is keyed BY BUCKET.
        plan = comm.build_sync_plan(pshapes, pspecs, tcfg.sync, dp_total)
        rshapes = plan.residual_shapes()
        rspecs = plan.residual_specs(dp_ax)
    else:
        rshapes = rspecs = None
        if getattr(tcfg.sync, "output_mode", "replicated") == "scattered":
            raise ValueError(
                "output_mode='scattered' requires sync.mode='sparcml' "
                "(dense mode has no plan to scatter; use fsdp for ZeRO-3)")

    if plan is not None and plan.scattered:
        if not tcfg.zero1:
            raise ValueError(
                "output_mode='scattered' IS the sharded-optimizer layout "
                "— it requires zero1=True (DESIGN.md §11)")
        oshapes = zero_scattered_state_shapes(plan, tcfg)
        ospecs = zero_scattered_state_specs(plan, tcfg, dp_ax)
    elif tcfg.sync.mode == "sparcml" and tcfg.zero1:
        oshapes = zero1_state_shapes(pshapes, pspecs, tcfg, dp_total)
        ospecs = zero1_state_specs(pshapes, pspecs, tcfg, dp_ax)
    else:
        oshapes = jax.eval_shape(
            lambda p: init_opt_state(p, tcfg.optimizer), pshapes)
        n_opt = {"adamw": 2, "sgdm": 1}[tcfg.optimizer.kind]
        ospecs = {"mu": pspecs, "count": P()}
        if n_opt == 2:
            ospecs["nu"] = pspecs

    shapes = TrainState(params=pshapes, opt=oshapes, residuals=rshapes,
                        step=jax.ShapeDtypeStruct((), jnp.int32))
    specs = TrainState(params=pspecs, opt=ospecs, residuals=rspecs, step=P())
    if return_plan:
        return shapes, specs, plan
    return shapes, specs


def init_state(model: Model, tcfg: TrainConfig, mesh: Mesh, key=None) -> tuple:
    """Materialize a sharded TrainState (for smoke tests / examples)."""
    if key is None:
        key = jax.random.PRNGKey(tcfg.seed)
    shapes, specs = state_shapes(model, tcfg, mesh, key)

    def make():
        params = model.init(key)
        if tcfg.sync.mode == "sparcml" and tcfg.zero1:
            opt = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), shapes.opt,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        else:
            opt = init_opt_state(params, tcfg.optimizer)
        res = None
        if shapes.residuals is not None:
            res = jax.tree.map(
                lambda s: None if s is None else jnp.zeros(s.shape, s.dtype),
                shapes.residuals, is_leaf=lambda x: x is None or isinstance(x, jax.ShapeDtypeStruct))
        return TrainState(params, opt, res, jnp.zeros((), jnp.int32))

    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s if s is not None else P()),
        specs, is_leaf=lambda x: x is None or isinstance(x, P))
    with mesh:
        state = jax.jit(make, out_shardings=shardings)()
    return state, specs


def batch_specs(cfg: ModelConfig, mesh: Mesh) -> dict:
    dp = dp_axes_of(mesh)
    out = {"tokens": P(dp, None), "labels": P(dp, None)}
    if cfg.family == "vlm":
        out["image_embeds"] = P(dp, None, None)
    if cfg.family == "encoder":
        out["frames"] = P(dp, None, None)
    return out


# --------------------------------------------------------------------------
# Guarded-step helpers (DESIGN.md §12): in-graph all-finite check over the
# raw gradient leaves, plus the chaos harness's in-graph injection. Shared
# by the pipelined step body so the guard's semantics cannot drift between
# lowerings.
# --------------------------------------------------------------------------

def all_finite_leaves(leaves) -> jax.Array:
    """f32 scalar: 1.0 iff every element of every leaf is finite. Checked
    on the RAW grads (before the reduce half) — in a staleness-1 pipeline
    a NaN entering reduce poisons residuals the same step, while the
    grad-norm of the APPLIED (stale, clean) buffers stays finite until
    the next step, so any later check point misses the corruption."""
    fin = jnp.ones((), jnp.float32)
    for g in leaves:
        fin = fin * jnp.all(jnp.isfinite(g)).astype(jnp.float32)
    return fin


def inject_nonfinite_leaves(leaves, fault_vec):
    """Overwrite grad leaf i with NaN (flag 1) or Inf (flag 2) where the
    (n_leaves,) ``fault_vec`` is nonzero. A pure SELECT (``jnp.where``),
    never additive — ``g + flag * nan`` would be NaN even at flag 0. With
    an all-zero vector every where picks the clean branch, so a bound but
    idle injector is bit-exact with no injector at all."""
    out = []
    for i, g in enumerate(leaves):
        flag = fault_vec[i]
        bad = jnp.where(flag > 1.5, jnp.inf, jnp.nan).astype(g.dtype)
        out.append(jnp.where(flag > 0.5, bad, g))
    return out


def guard_select(fin, new_tree, old_tree):
    """Elementwise select between the stepped and the pre-step tree on
    the guard verdict: ``fin`` 1.0 keeps ``new_tree`` bit-exactly (a
    select, so unselected NaNs never propagate), 0.0 rolls every leaf
    back to ``old_tree`` — the EF-preservation invariant: a tripped step
    leaves params, optimizer moments, residuals, and in-flight buffers
    exactly as they were."""
    if fin is None:
        return new_tree
    pred = fin > 0.5
    return jax.tree.map(lambda a, b: jnp.where(pred, a, b),
                        new_tree, old_tree)


# --------------------------------------------------------------------------
# Gradient computation with microbatch accumulation
# --------------------------------------------------------------------------

def _accumulated_grads(model: Model, params, batch, n_micro: int,
                       mesh: Mesh | None = None):
    """Mean loss + mean grads over n_micro microbatches (lax.scan).

    The (B,...) -> (n_micro, B/n_micro, ...) reshape must KEEP the dp
    sharding on the batch dim (axis 1 after reshape) — otherwise XLA puts
    'data' on the microbatch axis and every device materializes the whole
    microbatch (16x activation blowup, found via dry-run memory_analysis).
    """
    if n_micro == 1:
        loss, grads = jax.value_and_grad(lambda p: model.loss(p, batch))(params)
        return loss, grads

    def micro(batch_i):
        return jax.value_and_grad(lambda p: model.loss(p, batch_i))(params)

    def reshape_keep_dp(x):
        out = x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])
        if mesh is not None:
            dp = list(dp_axes_of(mesh))
            # drop leading dp axes until the microbatch rows divide evenly
            while dp and out.shape[1] % int(np.prod([mesh.shape[a] for a in dp])):
                dp.pop(0)
            if dp:
                spec = P(None, tuple(dp), *([None] * (out.ndim - 2)))
                out = jax.lax.with_sharding_constraint(
                    out, NamedSharding(mesh, spec))
        return out

    mb = jax.tree.map(reshape_keep_dp, batch)
    zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def body(carry, batch_i):
        acc_loss, acc_g = carry
        loss, g = micro(batch_i)
        acc_g = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), acc_g, g)
        return (acc_loss + loss, acc_g), None

    (loss, grads), _ = jax.lax.scan(body, (jnp.zeros(()), zero), mb)
    inv = 1.0 / n_micro
    grads = jax.tree.map(lambda g: (g * inv), grads)
    return loss * inv, grads


# --------------------------------------------------------------------------
# sparcml-mode inner step (manual over dp, auto over 'model')
# --------------------------------------------------------------------------

def _zero1_update(params, grads, opt, lr, tcfg: TrainConfig, pspecs,
                  dp_axes, dp_index, dp_total, gather_ctxs):
    """Each rank updates its canonical column slice, then all-gathers.

    gather_ctxs: one CollectiveContext per dp axis (innermost last) — the
    slice gather uses the same native/emulated collective flavor as the
    sync executor (DESIGN.md §4)."""
    sync = tcfg.sync
    leaves_p, treedef = jax.tree.flatten(params)
    leaves_g = treedef.flatten_up_to(grads)
    leaves_s = treedef.flatten_up_to(pspecs)
    leaves_mu = treedef.flatten_up_to(opt["mu"])
    leaves_nu = treedef.flatten_up_to(opt["nu"]) if "nu" in opt else [None] * len(leaves_p)

    count = opt["count"] + 1
    ocfg = tcfg.optimizer
    b1, b2 = ocfg.beta1, ocfg.beta2
    c1 = 1.0 - b1 ** count.astype(jnp.float32)
    c2 = 1.0 - b2 ** count.astype(jnp.float32)

    new_p, new_mu, new_nu = [], [], []
    for pl, gl, sl, mul, nul in zip(leaves_p, leaves_g, leaves_s, leaves_mu, leaves_nu):
        pc = comp.to_canonical(pl, sl, sync.bucket_size)        # (c, mB)
        gc = comp.to_canonical(gl, sl, sync.bucket_size)
        w = pc.shape[1] // dp_total
        my_p = jax.lax.dynamic_slice_in_dim(pc, dp_index * w, w, axis=1)
        my_g = jax.lax.dynamic_slice_in_dim(gc, dp_index * w, w, axis=1).astype(jnp.float32)
        m = mul[0].astype(jnp.float32)                          # strip replica axis
        if ocfg.kind == "adamw":
            v = nul[0].astype(jnp.float32)
            m2 = b1 * m + (1 - b1) * my_g
            v2 = b2 * v + (1 - b2) * my_g * my_g
            step = (m2 / c1) / (jnp.sqrt(v2 / c2) + ocfg.eps)
            step = step + ocfg.weight_decay * my_p.astype(jnp.float32)
            new_nu.append(v2.astype(nul.dtype)[None])
        else:
            m2 = ocfg.momentum * m + my_g
            step = m2
            new_nu.append(None)
        upd = (my_p.astype(jnp.float32) - lr * step).astype(pl.dtype)
        new_mu.append(m2.astype(mul.dtype)[None])
        # all-gather updated slices back to the full canonical layout
        full = upd
        for ctx in reversed(gather_ctxs):
            full = ctx.all_gather(full, axis=1)
        new_p.append(comp.from_canonical(full, pl.shape, sl))
    out_opt = {"mu": treedef.unflatten(new_mu), "count": count}
    if "nu" in opt:
        out_opt["nu"] = treedef.unflatten(new_nu)
    return treedef.unflatten(new_p), out_opt


def _zero1_update_spmd(params, grads, opt, lr, tcfg: TrainConfig, pspecs,
                       dp_total):
    """ZeRO-1 chunked update as plain auto-SPMD array ops: all ranks'
    chunks live on the leading (dp_total,) axis of the opt state, so the
    per-chunk math of :func:`_zero1_update` vectorizes over it — bitwise
    the same values, no shard_map (DESIGN.md §4.2)."""
    sync = tcfg.sync
    leaves_p, treedef = jax.tree.flatten(params)
    leaves_g = treedef.flatten_up_to(grads)
    leaves_s = treedef.flatten_up_to(pspecs)
    leaves_mu = treedef.flatten_up_to(opt["mu"])
    leaves_nu = treedef.flatten_up_to(opt["nu"]) if "nu" in opt else [None] * len(leaves_p)

    count = opt["count"] + 1
    ocfg = tcfg.optimizer
    b1, b2 = ocfg.beta1, ocfg.beta2
    c1 = 1.0 - b1 ** count.astype(jnp.float32)
    c2 = 1.0 - b2 ** count.astype(jnp.float32)

    new_p, new_mu, new_nu = [], [], []
    for pl, gl, sl, mul, nul in zip(leaves_p, leaves_g, leaves_s, leaves_mu, leaves_nu):
        pc = comp.to_canonical(pl, sl, sync.bucket_size)        # (rows, cols)
        gc = comp.to_canonical(gl, sl, sync.bucket_size)
        rows, cols = pc.shape
        w = cols // dp_total
        pch = pc.reshape(rows, dp_total, w).transpose(1, 0, 2)  # (dp, rows, w)
        gch = gc.reshape(rows, dp_total, w).transpose(1, 0, 2).astype(jnp.float32)
        m = mul.astype(jnp.float32)                             # (dp, rows, w)
        if ocfg.kind == "adamw":
            v = nul.astype(jnp.float32)
            m2 = b1 * m + (1 - b1) * gch
            v2 = b2 * v + (1 - b2) * gch * gch
            step = (m2 / c1) / (jnp.sqrt(v2 / c2) + ocfg.eps)
            step = step + ocfg.weight_decay * pch.astype(jnp.float32)
            new_nu.append(v2.astype(nul.dtype))
        else:
            m2 = ocfg.momentum * m + gch
            step = m2
            new_nu.append(None)
        upd = (pch.astype(jnp.float32) - lr * step).astype(pl.dtype)
        new_mu.append(m2.astype(mul.dtype))
        full = upd.transpose(1, 0, 2).reshape(rows, cols)
        new_p.append(comp.from_canonical(full, pl.shape, sl))
    out_opt = {"mu": treedef.unflatten(new_mu), "count": count}
    if "nu" in opt:
        out_opt["nu"] = treedef.unflatten(new_nu)
    return treedef.unflatten(new_p), out_opt


def _zero_scattered_update(params, reduced, opt, lr, tcfg: TrainConfig,
                           plan, coll):
    """ZeRO scattered update (DESIGN.md §11), manual lowering: consume the
    owner GRAD CHUNKS straight off the scattered reduce (no grad-side
    allgather ever ran), update my param/moment shard, then ONE dense
    param all_gather per BUCKET rebuilds the full params — the per-step
    collective count stays O(num_buckets), not O(num_leaves).

    reduced: bucket-keyed {name: (1, rows, w)} chunks (replica axis of
    size 1 inside shard_map); extra keys (the in-flight validity flag)
    are ignored. Returns (new_params, new_opt, grad_norm). The global
    grad norm is EXACT from the shards: owned ranges are disjoint and
    cover the buffers (padding contributes zero), so one scalar psum of
    the per-shard sums of squares is the global sum — only the summation
    order differs from the replicated path (allclose, not bitwise).
    """
    from repro.comm.buckets import pack_group, unpack_group

    sync = tcfg.sync
    ocfg = tcfg.optimizer
    p = plan.dp_total
    rank = coll.axis_rank()

    gnorm = jnp.sqrt(coll.psum(sum(
        jnp.sum(jnp.square(reduced[b.name][0].astype(jnp.float32)))
        for g in plan.groups for b in g.buckets)))
    factor = (jnp.minimum(1.0, ocfg.grad_clip / (gnorm + 1e-9))
              if ocfg.grad_clip else jnp.float32(1.0))

    count = opt["count"] + 1
    b1, b2 = ocfg.beta1, ocfg.beta2
    c1 = 1.0 - b1 ** count.astype(jnp.float32)
    c2 = 1.0 - b2 ** count.astype(jnp.float32)

    leaves_p, ptree = jax.tree.flatten(params)
    new_leaves: list = [None] * plan.num_leaves
    new_mu: dict = {}
    new_nu: dict = {}
    for group in plan.groups:
        pbuf = pack_group(group, leaves_p, sync.bucket_size)  # (rows, cols)
        parts = []
        for b in group.buckets:
            w = plan.owned_cols(b)
            seg = jax.lax.slice_in_dim(pbuf, b.col_start,
                                       b.col_start + b.cols, axis=1)
            my_p = jax.lax.dynamic_slice_in_dim(
                seg.reshape(group.rows, p, w), rank, 1, axis=1
            ).reshape(group.rows, w)
            g = reduced[b.name][0].astype(jnp.float32) * factor
            mul = opt["mu"][b.name]
            m = mul[0].astype(jnp.float32)
            if ocfg.kind == "adamw":
                nul = opt["nu"][b.name]
                v = nul[0].astype(jnp.float32)
                m2 = b1 * m + (1 - b1) * g
                v2 = b2 * v + (1 - b2) * g * g
                step = (m2 / c1) / (jnp.sqrt(v2 / c2) + ocfg.eps)
                step = step + ocfg.weight_decay * my_p
                new_nu[b.name] = v2.astype(nul.dtype)[None]
            else:
                m2 = ocfg.momentum * m + g
                step = m2
            new_mu[b.name] = m2.astype(mul.dtype)[None]
            upd = my_p - lr * step                            # f32 shard
            parts.append(coll.all_gather(upd, axis=1))        # (rows, b.cols)
        out_buf = parts[0] if len(parts) == 1 else jnp.concatenate(parts,
                                                                   axis=1)
        for leaf_id, arr in unpack_group(group, out_buf, leaves_p):
            new_leaves[leaf_id] = arr
    out_opt = {"mu": new_mu, "count": count}
    if ocfg.kind == "adamw":
        out_opt["nu"] = new_nu
    return ptree.unflatten(new_leaves), out_opt, gnorm


def _zero_scattered_update_spmd(params, grads, opt, lr, tcfg: TrainConfig,
                                plan):
    """Auto-SPMD twin of :func:`_zero_scattered_update`: moments live as
    full (dp_total, rows, w) bucket-chunk stacks, the per-chunk math
    vectorizes over the leading axis, and the param 'allgather' is the
    chunk->buffer reshape XLA re-materializes from the sharded stacks.
    ``grads`` are the CLIPPED synced leaves (the caller computes the clip
    exactly as the replicated reference so the factor — and therefore
    every parameter — is bitwise identical to replicated training).

    The params are deliberately NEVER packed into the group buffer here:
    only the moment-derived update direction flows through the bucket
    domain, and the actual parameter step — ``p - lr*(delta + wd*p)`` —
    runs per leaf with exactly the replicated :func:`adamw` fp ops.
    Packing the params alongside the vmapped grad computation trips a
    GSPMD partial-sum mislabel on the XLA-CPU fallback (the packed
    buffer comes back multiplied by dp_total); the delta-only
    formulation both avoids that and keeps per-coordinate bit parity
    with replicated training."""
    from repro.comm.buckets import from_canonical, pack_group

    sync = tcfg.sync
    ocfg = tcfg.optimizer
    p = plan.dp_total
    count = opt["count"] + 1
    b1, b2 = ocfg.beta1, ocfg.beta2
    c1 = 1.0 - b1 ** count.astype(jnp.float32)
    c2 = 1.0 - b2 ** count.astype(jnp.float32)

    leaves_p, ptree = jax.tree.flatten(params)
    leaves_g = ptree.flatten_up_to(grads)
    new_leaves: list = [None] * plan.num_leaves
    new_mu: dict = {}
    new_nu: dict = {}
    for group in plan.groups:
        gbuf = pack_group(group, leaves_g, sync.bucket_size)
        parts = []
        for b in group.buckets:
            w = plan.owned_cols(b)
            g = jax.lax.slice_in_dim(
                gbuf, b.col_start, b.col_start + b.cols, axis=1
            ).reshape(group.rows, p, w).transpose(1, 0, 2)  # (p, rows, w)
            mul = opt["mu"][b.name]
            m = mul.astype(jnp.float32)
            if ocfg.kind == "adamw":
                nul = opt["nu"][b.name]
                v = nul.astype(jnp.float32)
                m2 = b1 * m + (1 - b1) * g
                v2 = b2 * v + (1 - b2) * g * g
                delta = (m2 / c1) / (jnp.sqrt(v2 / c2) + ocfg.eps)
                new_nu[b.name] = v2.astype(nul.dtype)
            else:
                m2 = ocfg.momentum * m + g
                delta = m2
            new_mu[b.name] = m2.astype(mul.dtype)
            parts.append(delta.transpose(1, 0, 2).reshape(group.rows,
                                                          b.cols))
        dbuf = parts[0] if len(parts) == 1 else jnp.concatenate(parts,
                                                                axis=1)
        for slot in group.slots:
            seg = jax.lax.slice_in_dim(dbuf, slot.offset,
                                       slot.offset + slot.cols, axis=1)
            delta_leaf = from_canonical(seg, slot.shape, slot.spec)  # f32
            pl = leaves_p[slot.leaf_id]
            pf = pl.astype(jnp.float32)
            step = delta_leaf
            if ocfg.kind == "adamw":
                step = step + ocfg.weight_decay * pf
            new_leaves[slot.leaf_id] = (pf - lr * step).astype(pl.dtype)
    out_opt = {"mu": new_mu, "count": count}
    if ocfg.kind == "adamw":
        out_opt["nu"] = new_nu
    return ptree.unflatten(new_leaves), out_opt


def build_train_step(model: Model, tcfg: TrainConfig, mesh: Mesh):
    """Returns (jitted step fn(state, batch, key) -> (state, metrics),
    (state_shapes, state_specs))."""
    cfg = model.cfg
    sched = make_schedule(tcfg.schedule)
    shapes, specs, plan = state_shapes(model, tcfg, mesh, return_plan=True)
    bspecs = batch_specs(cfg, mesh)
    dp_ax = dp_axes_of(mesh)
    dp_total = dp_total_of(mesh)
    n_micro = tcfg.microbatches
    sh = lambda t: shardings_tree(mesh, t)

    if tcfg.sync.mode != "sparcml":
        # ---------------- dense mode: plain auto-SPMD jit ----------------
        import dataclasses
        from repro.models.model import Model as _M
        model = _M(dataclasses.replace(cfg, act_dp_axes=dp_ax))
        cfg_local = model.cfg  # noqa: F841

        def step_fn(state: TrainState, batch, key):
            lr = sched(state.step)
            with scopes.scope(scopes.MODEL):
                loss, grads = _accumulated_grads(
                    model, state.params, batch, n_micro, mesh=mesh)
            with scopes.scope(scopes.UPDATE):
                grads, gnorm = clip_by_global_norm(
                    grads, tcfg.optimizer.grad_clip)
                new_p, new_opt = opt_update(
                    state.params, grads, state.opt, lr, tcfg.optimizer)
            new_state = TrainState(new_p, new_opt, None, state.step + 1)
            return new_state, {"loss": loss, "grad_norm": gnorm, "lr": lr}

        jitted = jax.jit(
            step_fn,
            in_shardings=(sh(specs), sh(bspecs), NamedSharding(mesh, P())),
            out_shardings=(sh(specs), NamedSharding(mesh, P())),
            donate_argnums=(0,),
        )
        return jitted, (shapes, specs)

    # ---------------- sparcml mode: manual dp, auto model ----------------
    # Native lax collectives inside the manual-dp shard_map region
    # (DESIGN.md §4); `plan` is the one state_shapes keyed the residual
    # dict with.
    pspecs = specs.params
    data_axis = dp_ax[-1]
    p_data = mesh.shape[data_axis]
    pod_axis = dp_ax[0] if len(dp_ax) > 1 else None
    p_pod = mesh.shape[pod_axis] if pod_axis else 1

    def inner(state: TrainState, batch, key, rid):
        # batch arrives as this rank's rows (split over dp by in_specs);
        # rid is this rank's flat dp index fed AS DATA (a (1,) slice of
        # arange(dp_total)) — jax.lax.axis_index does not lower in
        # partial-manual regions on the emulated backends.
        lr = sched(state.step)
        with scopes.scope(scopes.MODEL):
            loss, grads = _accumulated_grads(model, state.params, batch,
                                             n_micro)
        loss = jax.lax.pmean(loss, dp_ax[-1])
        if len(dp_ax) > 1:
            loss = jax.lax.pmean(loss, dp_ax[0])
        dp_index = rid[0]
        data_rank = dp_index % p_data
        pod_rank = dp_index // p_data if pod_axis else None
        leaves_g, gtree = jax.tree.flatten(grads)
        if plan.scattered:
            # Scattered (DESIGN.md §11): the reduce stops at the owner
            # shard, the update runs there, and the only gather left is
            # the dense param allgather inside the update (one per
            # bucket). Grad norm comes back exactly from the shards.
            with scopes.scope(scopes.SYNC):
                reduced, new_res, _ = comm.reduce_buckets(
                    plan, leaves_g, state.residuals, key,
                    data_axis=data_axis, p_data=p_data,
                    pod_axis=pod_axis, p_pod=p_pod,
                    data_rank=data_rank, pod_rank=pod_rank)
            coll = comm.CollectiveContext(data_axis, p_data, rank=data_rank)
            with scopes.scope(scopes.UPDATE):
                new_p, new_opt, gnorm = _zero_scattered_update(
                    state.params, reduced, state.opt, lr, tcfg, plan, coll)
            new_state = TrainState(new_p, new_opt, new_res, state.step + 1)
            return new_state, {"loss": loss, "grad_norm": gnorm, "lr": lr}
        with scopes.scope(scopes.SYNC):
            synced_leaves, new_res = comm.execute_plan(
                plan, leaves_g, state.residuals, key,
                data_axis=data_axis, p_data=p_data,
                pod_axis=pod_axis, p_pod=p_pod,
                data_rank=data_rank, pod_rank=pod_rank,
            )
        synced = gtree.unflatten(synced_leaves)
        with scopes.scope(scopes.UPDATE):
            synced, gnorm = clip_by_global_norm(synced,
                                                tcfg.optimizer.grad_clip)
            if tcfg.zero1:
                gather_ctxs = [
                    comm.CollectiveContext(ax, mesh.shape[ax],
                                           rank=(pod_rank if ax == pod_axis
                                                 else data_rank))
                    for ax in dp_ax
                ]
                new_p, new_opt = _zero1_update(
                    state.params, synced, state.opt, lr, tcfg, pspecs,
                    dp_ax, dp_index, dp_total, gather_ctxs)
            else:
                new_p, new_opt = opt_update(
                    state.params, synced, state.opt, lr, tcfg.optimizer)
        new_state = TrainState(new_p, new_opt, new_res, state.step + 1)
        return new_state, {"loss": loss, "grad_norm": gnorm, "lr": lr}

    in_state_specs = manual_only_tree(specs)
    in_batch_specs = manual_only_tree(bspecs)

    rid_spec = P(tuple(dp_ax))
    mapped = compat.shard_map(
        inner, mesh=mesh,
        in_specs=(in_state_specs, in_batch_specs, P(), rid_spec),
        out_specs=(in_state_specs, P()),
        check_vma=False,
        axis_names=set(dp_ax),
    )

    def stepped(state: TrainState, batch, key):
        # rank-id feed: each rank's slice of arange(dp_total) — see inner.
        rid = jnp.arange(dp_total, dtype=jnp.int32)
        return mapped(state, batch, key, rid)

    jitted = jax.jit(
        stepped,
        in_shardings=(sh(specs), sh(bspecs), NamedSharding(mesh, P())),
        out_shardings=(sh(specs), NamedSharding(mesh, P())),
        donate_argnums=(0,),
    )
    return jitted, (shapes, specs)
