"""Fusion-bucket plan layer: pack/unpack round-trips (property tests over
leaf mixes incl. model-sharded leaves and padded tails), plan invariants,
manual/emulated/auto-SPMD executor parity, and the headline scaling claim:
the number of data-axis collectives per step is O(num_buckets), NOT
O(num_leaves) — asserted by counting collectives in the jaxpr."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro import comm
from repro.compat import shard_map
from repro.core import topk as topk_mod
from repro.core.compressor import SyncConfig


def _leaf_mix(seed, n_leaves, model_frac=0.3):
    """A reproducible mixed tree: flat leaves of odd sizes (padded tails)
    plus model-sharded 2-D leaves."""
    rng = np.random.default_rng(seed)
    shapes, specs = {}, {}
    for i in range(n_leaves):
        if rng.random() < model_frac:
            rows = int(rng.choice([8, 16]))
            cols = int(rng.integers(1, 40)) * 16
            shapes[f"w{i}"] = jax.ShapeDtypeStruct((cols, rows), jnp.float32)
            specs[f"w{i}"] = P(None, "model")
        else:
            n = int(rng.integers(3, 2000))        # deliberately ragged
            shapes[f"b{i}"] = jax.ShapeDtypeStruct((n,), jnp.float32)
            specs[f"b{i}"] = P()
    return shapes, specs


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**16), n_leaves=st.integers(2, 12),
       dp=st.sampled_from([2, 4]), bucket=st.sampled_from([64, 128]))
def test_pack_unpack_roundtrip(seed, n_leaves, dp, bucket):
    cfg = SyncConfig(mode="sparcml", bucket_size=bucket, min_sparse_size=1,
                     fusion_bucket_bytes=1 << 14)
    shapes, specs = _leaf_mix(seed, n_leaves)
    plan = comm.build_sync_plan(shapes, specs, cfg, dp)
    rng = np.random.default_rng(seed + 1)
    tree = {k: jnp.asarray(rng.standard_normal(s.shape).astype(np.float32))
            for k, s in shapes.items()}
    leaves = jax.tree.leaves(tree)
    # every leaf is covered exactly once (small leaves are fused, not
    # dropped to a side path)
    assert plan.covered_leaf_ids() == set(range(len(leaves)))
    for g in plan.groups:
        buf = comm.pack_group(g, leaves, cfg.bucket_size)
        assert buf.shape == (g.rows, g.cols)
        # bucket boundaries tile the group exactly, quantum-aligned
        q = comm.plan._col_quantum(cfg, dp)
        assert sum(b.cols for b in g.buckets) == g.cols
        assert all(b.cols % q == 0 for b in g.buckets)
        for leaf_id, back in comm.unpack_group(g, buf, leaves):
            np.testing.assert_array_equal(np.asarray(back),
                                          np.asarray(leaves[leaf_id]))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_bucket_count_matches_ceil_bound(seed):
    """<= ceil(total_canonical_bytes / fusion_bucket_bytes) + one partial
    bucket per group (flat leaves share ONE group, so the flat bucket
    count meets the ceil bound exactly)."""
    cfg = SyncConfig(mode="sparcml", bucket_size=512, min_sparse_size=1,
                     fusion_bucket_bytes=1 << 16)
    rng = np.random.default_rng(seed)
    shapes = {f"b{i}": jax.ShapeDtypeStruct((int(rng.integers(100, 30000)),),
                                            jnp.float32)
              for i in range(10)}
    specs = {k: P() for k in shapes}
    plan = comm.build_sync_plan(shapes, specs, cfg, 4)
    assert len(plan.groups) == 1
    g = plan.groups[0]
    cap_cols = comm.plan._bucket_capacity_cols(cfg, 4, 1)
    assert len(g.buckets) == math.ceil(g.cols / cap_cols)


def test_per_leaf_plan_matches_legacy_routing():
    cfg = SyncConfig(mode="sparcml", bucket_size=512, min_sparse_size=65536)
    shapes = {"big": jax.ShapeDtypeStruct((1 << 17,), jnp.float32),
              "small": jax.ShapeDtypeStruct((128,), jnp.float32)}
    specs = {"big": P(), "small": P()}
    plan = comm.build_per_leaf_plan(shapes, specs, cfg, 4)
    assert plan.num_buckets == 1          # only the big leaf qualifies
    fused = comm.build_sync_plan(shapes, specs, cfg, 4)
    assert fused.covered_leaf_ids() == {0, 1}   # fusion covers both


# --------------------------------------------------------------------------
# Executor parity: manual(native) == manual(emulated) == auto-SPMD
# --------------------------------------------------------------------------

def _toy_setup(qsgd_bits=None):
    cfg = SyncConfig(mode="sparcml", k_per_bucket=8, bucket_size=128,
                     algorithm="dsar_split_allgather", min_sparse_size=1024,
                     qsgd_bits=qsgd_bits, qsgd_bucket=128, impl="ref",
                     fusion_bucket_bytes=1 << 14)
    shapes = {"a": jax.ShapeDtypeStruct((3000,), jnp.float32),
              "b": jax.ShapeDtypeStruct((77,), jnp.float32),
              "c": jax.ShapeDtypeStruct((513,), jnp.float32)}
    specs = {"a": P(), "b": P(), "c": P()}
    plan = comm.build_sync_plan(shapes, specs, cfg, 8)
    key = jax.random.PRNGKey(3)
    grads_r = {k: jax.random.normal(jax.random.fold_in(key, i),
                                    (8,) + s.shape)
               for i, (k, s) in enumerate(shapes.items())}
    res = plan.init_residuals()
    return cfg, plan, grads_r, res


@pytest.mark.parametrize("qsgd_bits", [None, 4])
def test_executor_parity_manual_vs_spmd(mesh8, qsgd_bits):
    cfg, plan, grads_r, res = _toy_setup(qsgd_bits)
    key = jax.random.PRNGKey(9)

    def manual(gr, r, native):
        g = jax.tree.map(lambda x: x[0], gr)
        leaves, tree = jax.tree.flatten(g)
        rank = jax.lax.axis_index("data")
        out, new_res = comm.execute_plan(
            plan, leaves, r, key, data_axis="data", p_data=8,
            native=native, data_rank=None if native else rank)
        return tree.unflatten(out), new_res

    rspecs = {k: P("data", None, None) for k in res}
    outs = {}
    for native in (True, False):
        f = shard_map(lambda gr, r: manual(gr, r, native), mesh=mesh8,
                      in_specs=({k: P("data", None) for k in grads_r},
                                rspecs),
                      out_specs=({k: P() for k in grads_r}, rspecs),
                      check_vma=False)
        outs[native] = f(grads_r, res)
    # auto-SPMD formulation outside any shard_map
    leaves_r, tree = jax.tree.flatten(grads_r)
    spmd_leaves, spmd_res = comm.execute_plan_spmd(
        plan, leaves_r, res, key, p_data=8)
    spmd_out = tree.unflatten(spmd_leaves)

    for k in grads_r:
        a = np.asarray(outs[True][0][k])
        b = np.asarray(outs[False][0][k])
        c = np.asarray(spmd_out[k])
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(a, c, rtol=1e-5, atol=1e-6)
    for name in res:
        np.testing.assert_allclose(np.asarray(outs[True][1][name]),
                                   np.asarray(spmd_res[name]),
                                   rtol=1e-5, atol=1e-6)


def test_executor_parity_size1_pod_qsgd():
    """A size-1 pod axis must not perturb the QSGD rounding keys: the
    manual lowering (which sees pod_rank=0) and the auto-SPMD lowering
    (which skips the degenerate pod fold) must produce identical bits."""
    from repro.compat import make_mesh

    cfg, plan, grads_r, res = _toy_setup(qsgd_bits=4)
    mesh = make_mesh((1, 8), ("pod", "data"))
    key = jax.random.PRNGKey(9)

    def manual(gr, r):
        g = jax.tree.map(lambda x: x[0], gr)
        leaves, tree = jax.tree.flatten(g)
        out, new_res = comm.execute_plan(
            plan, leaves, r, key, data_axis="data", p_data=8,
            pod_axis="pod", p_pod=1)
        return tree.unflatten(out), new_res

    rspecs = {k: P(("pod", "data"), None, None) for k in res}
    f = shard_map(manual, mesh=mesh,
                  in_specs=({k: P(("pod", "data"), None) for k in grads_r},
                            rspecs),
                  out_specs=({k: P() for k in grads_r}, rspecs),
                  check_vma=False)
    man_out, _ = f(grads_r, res)
    leaves_r, tree = jax.tree.flatten(grads_r)
    spmd_leaves, _ = comm.execute_plan_spmd(plan, leaves_r, res, key,
                                            p_data=8, p_pod=1)
    spmd_out = tree.unflatten(spmd_leaves)
    for k in grads_r:
        np.testing.assert_allclose(np.asarray(man_out[k]),
                                   np.asarray(spmd_out[k]),
                                   rtol=1e-5, atol=1e-6)


def test_fused_matches_oracle(mesh8):
    """Fused bucket sync == hand-computed pack -> per-rank TopK -> mean."""
    cfg, plan, grads_r, res = _toy_setup()
    key = jax.random.PRNGKey(1)
    leaves_r, tree = jax.tree.flatten(grads_r)
    out_leaves, _ = comm.execute_plan_spmd(plan, leaves_r, res, key, p_data=8)
    out = tree.unflatten(out_leaves)

    # oracle over the single flat group
    (g,) = plan.groups
    packed = np.stack([
        np.asarray(comm.pack_group(g, [l[r] for l in leaves_r],
                                   cfg.bucket_size))
        for r in range(8)
    ])                                                   # (8, 1, cols)
    dens = []
    for r in range(8):
        u, _ = topk_mod.compress2d(jnp.asarray(packed[r]), cfg.k_per_bucket,
                                   cfg.bucket_size)
        dens.append(np.asarray(u.densify()))
    oracle_buf = np.stack(dens).sum(0) / 8.0
    for leaf_id, arr in comm.unpack_group(g, jnp.asarray(oracle_buf),
                                          [l[0] for l in leaves_r]):
        np.testing.assert_allclose(np.asarray(out_leaves[leaf_id]),
                                   np.asarray(arr), rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------
# The headline claim: collectives per step scale with buckets, not leaves
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


def test_step_collectives_scale_with_buckets_not_leaves(mesh8):
    """>= 8 sparse-path leaves lower to <= ceil(total_canonical_bytes /
    fusion_bucket_bytes) data-axis SPARSE collectives (one fused a2a per
    DSAR bucket), where the per-leaf pipeline paid one per leaf."""
    n_leaves = 10
    cfg = SyncConfig(mode="sparcml", k_per_bucket=8, bucket_size=512,
                     algorithm="dsar_split_allgather", min_sparse_size=1024,
                     impl="ref", fusion_bucket_bytes=1 << 18)
    shapes = {f"w{i}": jax.ShapeDtypeStruct((16384,), jnp.float32)
              for i in range(n_leaves)}
    specs = {k: P() for k in shapes}
    plan = comm.build_sync_plan(shapes, specs, cfg, 8)
    assert plan.num_sparse_buckets >= 1
    total_bytes = sum(
        g.rows * g.cols * 4 for g in plan.groups)
    ceil_bound = math.ceil(total_bytes / cfg.fusion_bucket_bytes)
    assert plan.num_buckets <= ceil_bound
    # legacy routing would have dense-psum'd NONE of these (all above
    # min_sparse_size=1024) but paid one collective pipeline per leaf;
    # with paper-default min_sparse_size every one fell to dense psum.
    assert comm.build_per_leaf_plan(
        shapes, specs,
        SyncConfig(mode="sparcml", bucket_size=512), 8).num_buckets == 0

    res = plan.init_residuals()
    key = jax.random.PRNGKey(0)

    def sync(gr, r):
        g = jax.tree.map(lambda x: x[0], gr)
        leaves, tree = jax.tree.flatten(g)
        out, new_res = comm.execute_plan(plan, leaves, r, key,
                                         data_axis="data", p_data=8)
        return tree.unflatten(out), new_res

    rspecs = {k: P("data", None, None) for k in res}
    f = shard_map(sync, mesh=mesh8,
                  in_specs=({k: P("data", None) for k in shapes}, rspecs),
                  out_specs=({k: P() for k in shapes}, rspecs),
                  check_vma=False)
    grads_r = {k: jnp.ones((8,) + s.shape, jnp.float32)
               for k, s in shapes.items()}
    jaxpr = jax.make_jaxpr(f)(grads_r, res).jaxpr
    n_a2a = _count_prims(jaxpr, {"all_to_all"})
    assert n_a2a == plan.num_sparse_buckets, (n_a2a, plan.describe())
    assert n_a2a <= ceil_bound
    assert n_a2a < n_leaves
    # and the result is still correct: identical all-ones ranks mean back
    # to the TopK selection — k of every bucket survive at value 1.0
    out, _ = f(grads_r, res)
    per_leaf_selected = 16384 // cfg.bucket_size * cfg.k_per_bucket
    np.testing.assert_allclose(np.asarray(out["w0"]).sum(),
                               per_leaf_selected, rtol=1e-5)


def test_full_train_step_collective_count():
    """The acceptance claim end-to-end: a real train step whose model has
    >= 8 sparse-path leaves lowers to <= ceil(total_canonical_bytes /
    fusion_bucket_bytes) data-axis sparse collectives (an 8x1 mesh takes
    the manual/native lowering — the trivial model axis creates no
    subgroups — so the a2a count IS the DSAR bucket count)."""
    from repro.compat import make_mesh
    from repro.models.config import ModelConfig
    from repro.models.model import build_model
    from repro.optim.optimizers import OptimizerConfig
    from repro.optim.schedule import ScheduleConfig
    from repro.train.state import TrainConfig
    from repro.train.train_step import build_train_step

    mesh = make_mesh((8, 1), ("data", "model"))
    cfg = ModelConfig(name="ts", family="dense", num_layers=2, d_model=256,
                      num_heads=4, num_kv_heads=2, d_ff=512, vocab_size=512,
                      dtype=jnp.float32, param_dtype=jnp.float32,
                      max_seq_len=64)
    sync = SyncConfig(mode="sparcml", k_per_bucket=8, bucket_size=128,
                      algorithm="dsar_split_allgather", min_sparse_size=1024,
                      impl="ref", fusion_bucket_bytes=1 << 20)
    tcfg = TrainConfig(sync=sync, optimizer=OptimizerConfig(),
                       schedule=ScheduleConfig(peak_lr=1e-3, warmup_steps=2,
                                               total_steps=10), zero1=False)
    model = build_model(cfg)
    with mesh:
        step_fn, (shapes, _) = build_train_step(model, tcfg, mesh)
        pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        from repro.models.specs import param_specs
        plan = comm.build_sync_plan(pshapes, param_specs(pshapes, cfg, None),
                                    sync, 8)
        n_leaves = len(jax.tree.leaves(pshapes))
        assert n_leaves >= 8
        total_bytes = sum(g.rows * g.cols * 4 for g in plan.groups)
        ceil_bound = max(1, math.ceil(total_bytes / sync.fusion_bucket_bytes))
        b = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        jaxpr = jax.make_jaxpr(step_fn)(shapes, b, key).jaxpr
    n_a2a = _count_prims(jaxpr, {"all_to_all"})
    assert 1 <= n_a2a == plan.num_sparse_buckets <= ceil_bound, (
        n_a2a, ceil_bound, plan.describe())
    assert n_a2a < n_leaves
