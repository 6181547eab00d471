"""ZeRO-sharded training state (DESIGN.md §11): scattered output mode.

Covers the PR's acceptance surface:
  * SyncPlan.wire_bytes per-rank vs aggregate conventions, and the
    scattered-mode wire win over the replicated ssar_* exchanges;
  * scattered-vs-replicated training parity on the auto-SPMD and
    manual lowerings (>= 2 EF steps each);
  * emulated-lowering owner chunks == column slices of the replicated
    reduce (exact), with residual carry;
  * shard mass conservation when the portfolio capacity caps bind;
  * checkpoint interop in BOTH directions (zero_scattered <->
    zero1_leaf), in memory and through the Trainer's on-disk restore;
  * the pipelined scattered step's param allgather stays O(num_buckets).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import comm
from repro.compat import make_mesh, shard_map
from repro.core.compressor import SyncConfig
from repro.data.pipeline import DataConfig, synthetic_batch
from repro.models.config import ModelConfig
from repro.models.model import build_model
from repro.optim.optimizers import OptimizerConfig
from repro.optim.schedule import ScheduleConfig
from repro.train import checkpoint as ckpt
from repro.train.state import TrainConfig
from repro.train.train_step import (
    build_train_step,
    init_state,
    state_shapes,
)

KEY = jax.random.PRNGKey(0)
N, BUCKET, KPB = 8192, 128, 8


def _sync(mode, algorithm="dsar_split_allgather", k=KPB, **kw):
    base = dict(mode="sparcml", k_per_bucket=k, bucket_size=BUCKET,
                algorithm=algorithm, min_sparse_size=1024, impl="ref",
                fusion_bucket_bytes=1 << 18, output_mode=mode)
    base.update(kw)
    return SyncConfig(**base)


def _tcfg(mode, algorithm="dsar_split_allgather"):
    return TrainConfig(sync=_sync(mode, algorithm),
                       optimizer=OptimizerConfig(),
                       schedule=ScheduleConfig(peak_lr=3e-3, warmup_steps=2,
                                               total_steps=100),
                       zero1=True)


def _model_cfg():
    """Sized so the sparse path engages at dp=4 and dp=8 (canonical
    cols per bucket divide both)."""
    return ModelConfig(name="tz", family="dense", num_layers=2, d_model=512,
                       num_heads=8, num_kv_heads=4, d_ff=1024, vocab_size=512,
                       dtype=jnp.float32, param_dtype=jnp.float32,
                       max_seq_len=64)


def _flat_plan(mode, algorithm, k=KPB, dp=8, n=N):
    cfg = _sync(mode, algorithm, k=k, fusion_bucket_bytes=1 << 14)
    shapes = {"a": jax.ShapeDtypeStruct((n,), jnp.float32)}
    plan = comm.build_sync_plan(shapes, {"a": P()}, cfg, dp)
    sparse = [b.name for b in plan.buckets if b.sparse]
    assert sparse, plan.describe()
    return plan.replan(algorithms={nm: algorithm for nm in sparse})


def _run_steps(mesh, tcfg, n_steps=4, seed_offset=0):
    model = build_model(_model_cfg())
    step_fn, _ = build_train_step(model, tcfg, mesh)
    state, _ = init_state(model, tcfg, mesh)
    dcfg = DataConfig(global_batch=8, seq_len=32, vocab_size=512)
    losses = []
    with mesh:
        for i in range(n_steps):
            batch = jax.tree.map(jnp.asarray,
                                 synthetic_batch(dcfg, i + seed_offset))
            state, m = step_fn(state, batch, jax.random.fold_in(KEY, i))
            losses.append(float(m["loss"]))
    return losses, state


# --------------------------------------------------------------------------
# wire accounting (satellite: per-rank vs aggregate convention)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("algo", ["ssar_balanced_split",
                                  "ssar_rearranged_rs",
                                  "dsar_split_allgather"])
def test_wire_bytes_per_rank_vs_aggregate(algo):
    """wire_bytes() is PER RANK per step; aggregate=True is exactly p
    times that — both for the gradient exchange and the param
    allgather. Pins the convention so callers can't mix the two."""
    p = 8
    for mode in ("replicated", "scattered"):
        plan = _flat_plan(mode, algo, dp=p)
        per_rank = plan.wire_bytes()
        agg = plan.wire_bytes(aggregate=True)
        assert per_rank > 0
        assert agg == pytest.approx(p * per_rank, rel=1e-12)
        pg = plan.param_allgather_bytes()
        pg_agg = plan.param_allgather_bytes(aggregate=True)
        if mode == "replicated":
            assert pg == 0.0 and pg_agg == 0.0
        else:
            # every bucket ships its (P-1)/P foreign fp32 columns
            want = sum((p - 1) / p * b.n * 4 for b in plan.buckets)
            assert pg == pytest.approx(want)
            assert pg_agg == pytest.approx(p * pg, rel=1e-12)


@pytest.mark.parametrize("algo", ["ssar_balanced_split",
                                  "ssar_rearranged_rs"])
def test_scattered_wire_below_replicated_at_low_density(algo):
    """The tentpole wire claim: at d <= 1% the scattered gradient
    exchange is STRICTLY below the replicated ssar_* exchange (the
    skipped gather is the saving; the dense param allgather is
    accounted separately because it overlaps the next forward)."""
    k = 1                           # 1/128 per bucket < 1% density
    rep = _flat_plan("replicated", algo, k=k)
    sc = _flat_plan("scattered", algo, k=k)
    assert sc.wire_bytes() < rep.wire_bytes(), (
        algo, sc.wire_bytes(), rep.wire_bytes())
    assert sc.param_allgather_bytes() > 0


def test_scattered_plan_geometry_and_replan():
    plan = _flat_plan("scattered", "ssar_balanced_split")
    assert plan.scattered
    assert plan.signature().startswith("out=scattered|")
    for g in plan.groups:
        for b in g.buckets:
            assert plan.owned_cols(b) * plan.dp_total == b.cols
    # replanning (density drift, algorithm swap) must PRESERVE the
    # output mode — the state layout is pinned to it (DESIGN.md §11)
    re = plan.replan(algorithms={b.name: "ssar_rearranged_rs"
                                 for b in plan.buckets if b.sparse})
    assert re.scattered and re.signature().startswith("out=scattered|")


# --------------------------------------------------------------------------
# per-device state memory (satellite: dryrun breakdown)
# --------------------------------------------------------------------------

def test_state_memory_breakdown_scattered_shards_opt(mesh4x2):
    from repro.launch.dryrun import state_memory_breakdown

    model = build_model(_model_cfg())
    full = TrainConfig(sync=_sync("replicated"), optimizer=OptimizerConfig(),
                       schedule=ScheduleConfig(peak_lr=3e-3, warmup_steps=2,
                                               total_steps=100), zero1=False)
    scat = _tcfg("scattered")
    m_full = state_memory_breakdown(model, full, mesh4x2)
    m_scat = state_memory_breakdown(model, scat, mesh4x2)
    p = 4  # dp_total on mesh4x2
    assert m_full["params"] == m_scat["params"]
    # moments shard 1/P per device (bucket padding adds a little)
    assert m_scat["opt_mu"] <= m_full["opt_mu"] / p * 1.10
    assert m_scat["opt_nu"] <= m_full["opt_nu"] / p * 1.10
    assert m_scat["total"] < m_full["total"]
    assert m_scat["ef_residual"] > 0       # EF state is accounted
    for k in ("params", "opt_mu", "opt_nu", "ef_residual", "inflight",
              "total"):
        assert k in m_scat


# --------------------------------------------------------------------------
# training parity: scattered == replicated on every lowering
# --------------------------------------------------------------------------

def test_scattered_spmd_matches_replicated(mesh4x2):
    """Auto-SPMD lowering (mesh4x2 falls back on CPU), 4 steps — at
    least 2 with the EF residual warm. The scattered step rebuilds the
    synced leaves and reuses the replicated clip, so training tracks
    the replicated run to fp-fusion noise."""
    lr_, sr = _run_steps(mesh4x2, _tcfg("replicated"))
    ls_, ss = _run_steps(mesh4x2, _tcfg("scattered"))
    np.testing.assert_allclose(lr_, ls_, rtol=1e-5)
    # residuals exist and are warm (EF actually engaged)
    assert ss.residuals and any(
        float(jnp.abs(v).sum()) > 0 for v in jax.tree.leaves(ss.residuals))
    for a, b in zip(jax.tree.leaves(sr.params), jax.tree.leaves(ss.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_scattered_manual_matches_replicated():
    """Native manual lowering ((8,1) mesh): the reduce stops at the
    owner shard and the only gather left is the per-bucket dense param
    allgather. Grad norm comes from a per-shard psum (different fp
    summation order), so parity is allclose, not bitwise."""
    mesh = make_mesh((8, 1), ("data", "model"))
    lr_, sr = _run_steps(mesh, _tcfg("replicated", "ssar_balanced_split"))
    ls_, ss = _run_steps(mesh, _tcfg("scattered", "ssar_balanced_split"))
    np.testing.assert_allclose(lr_, ls_, rtol=1e-4)
    for a, b in zip(jax.tree.leaves(sr.params), jax.tree.leaves(ss.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=1e-2)


def test_scattered_emulated_chunks_match_replicated_slices(mesh8):
    """Emulated lowering (psum-only CollectiveContext): each reduced
    value is my (1, rows, cols/p) owned chunk and must equal the OWN
    column slice of the replicated reduce EXACTLY, with identical
    residual carry, over 2 EF steps."""
    rng = np.random.default_rng(3)
    grads = [jnp.asarray(rng.standard_normal((8, N)).astype(np.float32))
             for _ in range(2)]
    rep = _flat_plan("replicated", "ssar_rearranged_rs")
    sc = _flat_plan("scattered", "ssar_rearranged_rs")

    def run(plan, scattered):
        res = plan.init_residuals()
        rspecs = {k: P("data", None, None) for k in res}
        rid = jnp.arange(8, dtype=jnp.int32)
        out_specs = ({b.name: (P("data", None, None) if scattered else P())
                      for b in plan.buckets}, rspecs)

        def inner(g, r, rid):
            reduced, new_res, _ = comm.reduce_buckets(
                plan, [g[0]], r, KEY, data_axis="data", p_data=8,
                native=False, data_rank=rid[0])
            return reduced, new_res

        f = shard_map(inner, mesh=make_mesh((8,), ("data",)),
                      in_specs=(P("data", None), rspecs, P("data")),
                      out_specs=out_specs, check_vma=False)
        outs = []
        for g in grads:
            reduced, res = f(g, res, rid)
            outs.append({k: np.asarray(v) for k, v in reduced.items()})
        return outs, {k: np.asarray(v) for k, v in res.items()}

    out_r, res_r = run(rep, scattered=False)
    out_s, res_s = run(sc, scattered=True)
    for step in range(2):
        for g in sc.groups:
            for b in g.buckets:
                full = out_r[step][b.name]            # (rows, cols)
                chunks = out_s[step][b.name]          # (p, rows, w)
                w = sc.owned_cols(b)
                for r in range(8):
                    np.testing.assert_array_equal(
                        chunks[r], full[:, r * w:(r + 1) * w])
    for k in res_r:
        np.testing.assert_array_equal(res_r[k], res_s[k])


def test_shard_mass_conservation_under_caps(mesh8):
    """Random low-overlap grads make the balanced-split capacity clamp
    BIND. The owner shards must still conserve mass: per bucket,
    replicas * concat(shards) + sum_r residual_r == sum_r grad_r (the
    clamped-off mass lands in the owning rank's fold, never vanishes)."""
    plan = _flat_plan("scattered", "ssar_balanced_split")
    rng = np.random.default_rng(7)
    g = jnp.asarray(rng.standard_normal((8, N)).astype(np.float32))
    res = plan.init_residuals()
    rspecs = {k: P("data", None, None) for k in res}
    out_specs = ({b.name: P("data", None, None) for b in plan.buckets},
                 rspecs)

    rid = jnp.arange(8, dtype=jnp.int32)

    def inner(gr, r, rid):
        reduced, new_res, _ = comm.reduce_buckets(
            plan, [gr[0]], r, KEY, data_axis="data", p_data=8, native=False,
            data_rank=rid[0])
        return reduced, new_res

    f = shard_map(inner, mesh=mesh8,
                  in_specs=(P("data", None), rspecs, P("data")),
                  out_specs=out_specs, check_vma=False)
    reduced, new_res = f(g, res, rid)

    gnp = np.asarray(g)
    clamped_any = False
    for grp in plan.groups:
        for b in grp.buckets:
            seg = gnp[:, b.col_start:b.col_start + b.cols]
            exact = seg.sum(axis=0)                       # (cols,)
            chunks = np.asarray(reduced[b.name])          # (p, rows, w)
            merged = np.concatenate([chunks[r][0] for r in range(8)])
            r_sum = np.asarray(new_res[b.name])[:, 0, :].sum(axis=0)
            recon = 8.0 * merged + r_sum                  # mean=True scale
            np.testing.assert_allclose(recon, exact, rtol=1e-4, atol=1e-4)
            if not np.allclose(8.0 * merged, exact, atol=1e-6):
                clamped_any = True
    assert clamped_any, "caps never bound — test exercises nothing"


# --------------------------------------------------------------------------
# checkpoint interop: zero_scattered <-> zero1_leaf, both directions
# --------------------------------------------------------------------------

def _convert_state(state, plan, source, target):
    return ckpt.convert_opt_layout(state, plan, source=source, target=target)


def _resume_steps(mesh, tcfg, state, start, n_steps):
    model = build_model(_model_cfg())
    step_fn, _ = build_train_step(model, tcfg, mesh)
    dcfg = DataConfig(global_batch=8, seq_len=32, vocab_size=512)
    with mesh:
        for i in range(start, start + n_steps):
            batch = jax.tree.map(jnp.asarray, synthetic_batch(dcfg, i))
            state, _ = step_fn(state, batch, jax.random.fold_in(KEY, i))
    return state


@pytest.mark.parametrize("direction", ["scattered_to_replicated",
                                       "replicated_to_scattered"])
def test_checkpoint_interop_continues_training(mesh4x2, direction):
    """2 steps under one layout -> convert -> 2 more under the other
    == 4 straight steps under the target layout. The conversion is
    value-exact (pinned bitwise in the trainer test below); the
    tolerance here absorbs the lowering fp noise of the first two
    steps, which EF top-k selection can amplify on a few coordinates."""
    src_mode, dst_mode = (("scattered", "replicated")
                          if direction == "scattered_to_replicated"
                          else ("replicated", "scattered"))
    src_layout = ("zero_scattered" if src_mode == "scattered"
                  else "zero1_leaf")
    dst_layout = ("zero_scattered" if dst_mode == "scattered"
                  else "zero1_leaf")
    model = build_model(_model_cfg())
    _, _, plan = state_shapes(model, _tcfg(src_mode), mesh4x2,
                              return_plan=True)

    _, mid = _run_steps(mesh4x2, _tcfg(src_mode), n_steps=2)
    mid = _convert_state(mid, plan, src_layout, dst_layout)
    end = _resume_steps(mesh4x2, _tcfg(dst_mode), mid, start=2, n_steps=2)
    _, ref = _run_steps(mesh4x2, _tcfg(dst_mode), n_steps=4)
    for a, b in zip(jax.tree.leaves(ref.params), jax.tree.leaves(end.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-2, atol=5e-3)


def test_trainer_restores_other_layout_from_disk(mesh4x2, tmp_path):
    """On-disk interop through the Trainer: a checkpoint written under
    scattered (meta stamped zero_scattered) resumes under a replicated
    config — the moments come back converted, value-exact."""
    from repro.train.trainer import Trainer

    _, st = _run_steps(mesh4x2, _tcfg("scattered"), n_steps=2)
    ckpt.save(str(tmp_path), st, dp_total=4,
              opt_layout="zero_scattered")
    meta = ckpt.load_meta(str(tmp_path))
    assert meta["opt_layout"] == "zero_scattered"

    model = build_model(_model_cfg())
    dcfg = DataConfig(global_batch=8, seq_len=32, vocab_size=512)
    tr = Trainer(model, _tcfg("replicated"), mesh4x2, dcfg,
                 ckpt_dir=str(tmp_path))
    start = tr.init_or_resume()
    assert start == 2
    # structure matches the replicated (zero1_leaf) template...
    shapes, _, plan = state_shapes(model, _tcfg("replicated"), mesh4x2,
                                   return_plan=True)
    got = jax.tree_util.tree_structure(tr.state.opt)
    want = jax.tree_util.tree_structure(
        jax.tree.map(lambda s: 0, shapes.opt,
                     is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)))
    assert got == want
    # ...and the values are the converted scattered moments, exactly
    conv = _convert_state(st, plan, "zero_scattered", "zero1_leaf")
    for a, b in zip(jax.tree.leaves(conv.opt["mu"]),
                    jax.tree.leaves(tr.state.opt["mu"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_rejects_full_to_sharded():
    model = build_model(_model_cfg())
    mesh = make_mesh((4, 2), ("data", "model"))
    _, _, plan = state_shapes(model, _tcfg("scattered"), mesh,
                              return_plan=True)
    state, _ = init_state(model, _tcfg("scattered"), mesh)
    with pytest.raises(ValueError, match="only"):
        ckpt.convert_opt_layout(state, plan, source="full",
                                target="zero_scattered")


# --------------------------------------------------------------------------
# pipelined scattered step: param allgather is O(num_buckets)
# --------------------------------------------------------------------------

def _count_prims(jaxpr, names: set) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names:
            total += 1
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                total += _count_prims(sub, names)
    return total


try:  # moved out of jax.core in newer JAX
    from jax.extend.core import ClosedJaxpr as _ClosedJaxpr, Jaxpr as _Jaxpr
except ImportError:
    from jax.core import ClosedJaxpr as _ClosedJaxpr, Jaxpr as _Jaxpr


def _subjaxprs(v):
    out = []
    if isinstance(v, _ClosedJaxpr):
        out.append(v.jaxpr)
    elif isinstance(v, _Jaxpr):
        out.append(v)
    elif isinstance(v, (list, tuple)):
        for x in v:
            out.extend(_subjaxprs(x))
    return out


def test_pipelined_scattered_allgather_is_per_bucket():
    """The collective-count acceptance: on the native lowering the
    scattered pipelined step issues exactly ONE all_gather per fusion
    bucket (the dense param allgather) — not one per leaf — and fewer
    than the replicated zero1 step (whose DSAR gather phase + per-leaf
    param gathers both survive)."""
    from repro.runtime.pipeline import build_pipelined_step

    mesh = make_mesh((8, 1), ("data", "model"))
    model = build_model(_model_cfg())

    def trace(mode):
        tcfg = _tcfg(mode)
        with mesh:
            jitted, (shapes, _), plan = build_pipelined_step(
                model, tcfg, mesh, staleness=1, telemetry=False)
            b = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
            key = jax.ShapeDtypeStruct((2,), jnp.uint32)
            jaxpr = jax.make_jaxpr(jitted)(shapes, b, key).jaxpr
        return _count_prims(jaxpr, {"all_gather"}), plan

    n_scat, plan = trace("scattered")
    n_rep, _ = trace("replicated")
    n_leaves = plan.num_leaves
    assert n_scat == plan.num_buckets, (n_scat, plan.describe())
    assert plan.num_buckets < n_leaves  # fusion actually fuses here
    assert n_scat < n_rep, (n_scat, n_rep)
