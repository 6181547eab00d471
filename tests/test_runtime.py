"""Non-blocking runtime (DESIGN.md §6): staleness semantics and the
async driver.

* staleness=0 pipelined path == the synchronous executor on all three
  lowerings (manual native, manual psum-emulated, auto-SPMD);
* staleness=1 (one-step-stale gradients + error feedback) still descends
  on the convergence harness;
* the jaxpr collective count per pipelined step stays O(num_buckets)
  (also inside the scanned superstep);
* the scanned K-step superstep is exactly K sequential pipelined steps;
* the double-buffered driver changes scheduling, never numerics, and its
  checkpoints round-trip through the synchronous state shape.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import comm
from repro.compat import make_mesh
from repro.core import cost_model as cm
from repro.core.compressor import SyncConfig
from repro.data.pipeline import DataConfig, synthetic_batch
from repro.models.config import ModelConfig
from repro.models.model import build_model
from repro.optim.optimizers import OptimizerConfig
from repro.optim.schedule import ScheduleConfig
from repro.runtime import driver as rt_driver
from repro.runtime import pipeline as rt_pipeline
from repro.train.state import TrainConfig
from repro.train.train_step import build_train_step, init_state

from test_comm_plan import _count_prims


MODEL_CFG = ModelConfig(name="rt", family="dense", num_layers=2, d_model=64,
                        num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                        dtype=jnp.float32, param_dtype=jnp.float32,
                        max_seq_len=64)
SYNC = SyncConfig(mode="sparcml", k_per_bucket=8, bucket_size=128,
                  algorithm="dsar_split_allgather", min_sparse_size=1024,
                  impl="ref", fusion_bucket_bytes=1 << 18)
TCFG = TrainConfig(sync=SYNC, optimizer=OptimizerConfig(),
                   schedule=ScheduleConfig(peak_lr=3e-3, warmup_steps=5,
                                           total_steps=100),
                   zero1=True)
DCFG = DataConfig(global_batch=8, seq_len=32, vocab_size=256)
KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def mesh8x1():
    # dp-only (trivial model axis): the manual/native lowering executes
    # everywhere, so all three lowerings can be forced and compared.
    return make_mesh((8, 1), ("data", "model"))


@pytest.fixture(scope="module")
def model():
    return build_model(MODEL_CFG)


def _batch(i):
    return jax.tree.map(jnp.asarray, synthetic_batch(DCFG, i))


def _run(step_fn, state, n, start=0):
    losses = []
    for i in range(start, start + n):
        state, m = step_fn(state, _batch(i), jax.random.fold_in(KEY, i))
        losses.append(float(m["loss"]))
    return state, losses


def _assert_state_close(a, b, rtol=2e-4, atol=1e-5):
    # cross-lowering fp32 comparisons: different reduction orders diverge
    # by a few ulp per step (same tolerance class as executor parity)
    for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)
    for x, y in zip(jax.tree.leaves(a.opt), jax.tree.leaves(b.opt)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)
    for name in a.residuals:
        np.testing.assert_allclose(np.asarray(a.residuals[name]),
                                   np.asarray(b.residuals[name]),
                                   rtol=rtol, atol=atol)


# --------------------------------------------------------------------------
# (a) staleness=0 == synchronous executor, all three lowerings
# --------------------------------------------------------------------------

@pytest.mark.parametrize("lowering", ["manual", "emulated", "spmd"])
def test_staleness0_matches_synchronous(mesh8x1, model, lowering):
    with mesh8x1:
        sync_fn, _ = build_train_step(model, TCFG, mesh8x1)
        pipe_fn, _, plan = rt_pipeline.build_pipelined_step(
            model, TCFG, mesh8x1, staleness=0, lowering=lowering)
        assert plan.num_sparse_buckets >= 1
        s_sync, _ = init_state(model, TCFG, mesh8x1)
        s_pipe, _ = init_state(model, TCFG, mesh8x1)
        s_sync, l_sync = _run(sync_fn, s_sync, 3)
        s_pipe, l_pipe = _run(pipe_fn, s_pipe, 3)
    np.testing.assert_allclose(l_sync, l_pipe, rtol=1e-5)
    assert s_pipe.inflight is None
    # The lowerings compute each rank's gradient with different reduction
    # orders, so an EF accumulator entry can differ by an ulp. Where two
    # entries of one bucket tie in magnitude to that precision, top-k
    # keeps a different one of the pair: the residuals then swap the
    # two values, and the update differs at those two coordinates. Such
    # flips are the only differences allowed; all else stays allclose.
    flips = sum(_tie_flips(s_sync.residuals[n], s_pipe.residuals[n],
                           SYNC.bucket_size) for n in s_sync.residuals)
    for part, per_flip in (("params", 2), ("opt", 4)):   # opt: mu and nu
        n_off = sum(_n_not_close(x, y) for x, y in zip(
            jax.tree.leaves(getattr(s_sync, part)),
            jax.tree.leaves(getattr(s_pipe, part))))
        assert n_off <= per_flip * flips, (part, n_off, flips)


def _n_not_close(a, b, rtol=2e-4, atol=1e-5) -> int:
    return int(np.count_nonzero(~np.isclose(np.asarray(a), np.asarray(b),
                                            rtol=rtol, atol=atol)))


def _tie_flips(ra, rb, bucket_size, rtol=2e-4, atol=1e-5) -> int:
    """Number of top-k selections that differ between two EF residual
    buffers, asserting that each is a swap of a magnitude tie: in one
    bucket, the entry one run kept (and the other sent, leaving 0) pairs
    with an entry of the same magnitude, to a few ulps, the other way."""
    a = np.asarray(ra).reshape(-1, bucket_size)
    b = np.asarray(rb).reshape(-1, bucket_size)
    off = ~np.isclose(a, b, rtol=rtol, atol=atol)
    flips = 0
    for r in np.flatnonzero(off.any(axis=1)):
        cols = np.flatnonzero(off[r])
        kept_a = cols[b[r, cols] == 0]      # b sent it, a kept it
        kept_b = cols[a[r, cols] == 0]
        assert len(kept_a) + len(kept_b) == len(cols), (r, a[r, cols],
                                                        b[r, cols])
        assert len(kept_a) == len(kept_b), (r, kept_a, kept_b)
        np.testing.assert_allclose(np.sort(np.abs(a[r, kept_a])),
                                   np.sort(np.abs(b[r, kept_b])), rtol=1e-6)
        flips += len(kept_a)
    return flips


# --------------------------------------------------------------------------
# (b) staleness=1 still descends (convergence harness)
# --------------------------------------------------------------------------

def test_staleness1_descends(mesh8x1, model):
    with mesh8x1:
        pipe_fn, _, plan = rt_pipeline.build_pipelined_step(
            model, TCFG, mesh8x1, staleness=1)
        state, _ = init_state(model, TCFG, mesh8x1)
        state = rt_pipeline.attach_inflight(state, plan, mesh8x1)
        state, losses = _run(pipe_fn, state, 30)
    assert losses[-1] < losses[0] - 0.4, losses
    # the in-flight state really is live (holds the last reduction, and
    # is stamped valid so the next apply runs at full lr)
    assert state.inflight is not None
    assert float(state.inflight[rt_pipeline.VALID_KEY]) == 1.0
    assert any(float(jnp.abs(v).sum()) > 0
               for k, v in state.inflight.items()
               if k != rt_pipeline.VALID_KEY)


# --------------------------------------------------------------------------
# (c) collective count per pipelined step stays O(num_buckets)
# --------------------------------------------------------------------------

def test_pipelined_step_collective_count(mesh8x1, model):
    with mesh8x1:
        pipe_fn, (shapes, _), plan = rt_pipeline.build_pipelined_step(
            model, TCFG, mesh8x1, staleness=1, lowering="manual")
        b = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        jaxpr = jax.make_jaxpr(pipe_fn)(shapes, b, key).jaxpr
        n_a2a = _count_prims(jaxpr, {"all_to_all"})
        n_leaves = len(jax.tree.leaves(shapes.params))
        assert 1 <= n_a2a == plan.num_sparse_buckets < n_leaves, (
            n_a2a, plan.describe())

        # the scanned superstep traces its body ONCE: per-step count is
        # unchanged under K-step pipelining
        sup_fn, _, _ = rt_pipeline.build_superstep(
            model, TCFG, mesh8x1, staleness=1, steps=3, lowering="manual")
        bs = {"tokens": jax.ShapeDtypeStruct((3, 8, 32), jnp.int32),
              "labels": jax.ShapeDtypeStruct((3, 8, 32), jnp.int32)}
        keys = jax.ShapeDtypeStruct((3, 2), jnp.uint32)
        sup_jaxpr = jax.make_jaxpr(sup_fn)(shapes, bs, keys).jaxpr
        assert _count_prims(sup_jaxpr, {"all_to_all"}) == plan.num_sparse_buckets


# --------------------------------------------------------------------------
# superstep scan == sequential pipelined steps
# --------------------------------------------------------------------------

def test_superstep_matches_sequential(mesh8x1, model):
    k_steps = 3
    with mesh8x1:
        sup_fn, _, plan = rt_pipeline.build_superstep(
            model, TCFG, mesh8x1, staleness=1, steps=k_steps, donate=False)
        step_fn, _, _ = rt_pipeline.build_pipelined_step(
            model, TCFG, mesh8x1, staleness=1, donate=False)
        sa, _ = init_state(model, TCFG, mesh8x1)
        sb, _ = init_state(model, TCFG, mesh8x1)
        sa = rt_pipeline.attach_inflight(sa, plan, mesh8x1)
        sb = rt_pipeline.attach_inflight(sb, plan, mesh8x1)
        batches = [_batch(i) for i in range(k_steps)]
        keys = [jax.random.fold_in(KEY, i) for i in range(k_steps)]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)
        sa, ms = sup_fn(sa, stacked, jnp.stack(keys))
        seq_losses = []
        for i in range(k_steps):
            sb, mb = step_fn(sb, batches[i], keys[i])
            seq_losses.append(float(mb["loss"]))
    np.testing.assert_allclose(np.asarray(ms["loss"]), seq_losses, rtol=1e-5)
    _assert_state_close(sa, sb)
    for name in sa.inflight:
        np.testing.assert_allclose(np.asarray(sa.inflight[name]),
                                   np.asarray(sb.inflight[name]),
                                   rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------
# async driver: scheduling only, never numerics; checkpoint sync points
# --------------------------------------------------------------------------

def test_driver_matches_sequential(mesh8x1, model):
    n = 8
    with mesh8x1:
        fn, _, plan = rt_pipeline.build_superstep(
            model, TCFG, mesh8x1, staleness=1, steps=2)
        ref_fn, _, _ = rt_pipeline.build_pipelined_step(
            model, TCFG, mesh8x1, staleness=1, donate=False)
        state, _ = init_state(model, TCFG, mesh8x1)
        state = rt_pipeline.attach_inflight(state, plan, mesh8x1)
        state, log = rt_driver.run_pipelined(
            fn, state, start_step=0, num_steps=n,
            batch_fn=lambda s: synthetic_batch(DCFG, s),
            key_fn=lambda s: jax.random.fold_in(KEY, s),
            cfg=rt_driver.DriverConfig(depth=2, prefetch=2,
                                       steps_per_unit=2))
        ref, _ = init_state(model, TCFG, mesh8x1)
        ref = rt_pipeline.attach_inflight(ref, plan, mesh8x1)
        ref, ref_losses = _run(ref_fn, ref, n)
    assert len(log.losses) == n == len(log.step_times)
    assert int(state.step) == n
    np.testing.assert_allclose(log.losses, ref_losses, rtol=1e-5)
    _assert_state_close(state, ref)


def test_trainer_run_pipelined_checkpoints_interoperate(tmp_path):
    """Trainer.run_pipelined writes synchronous-shaped checkpoints (the
    in-flight buffers are stripped at the drain barrier), so a fresh
    Trainer resumes from them — in either loop."""
    from repro.train.trainer import Trainer

    mesh = make_mesh((8, 1), ("data", "model"))
    model = build_model(MODEL_CFG)
    ckpt_dir = str(tmp_path / "ckpt")
    tr = Trainer(model, TCFG, mesh, DCFG, ckpt_dir=ckpt_dir, ckpt_every=4)
    log = tr.run_pipelined(8, staleness=1, superstep=2, depth=2)
    assert len(log.losses) == 8
    assert int(tr.state.step) == 8
    assert tr.state.inflight is not None      # live pipelined state

    # fresh trainer resumes from the stripped checkpoint...
    tr2 = Trainer(model, TCFG, mesh, DCFG, ckpt_dir=ckpt_dir, ckpt_every=4)
    assert tr2.init_or_resume() == 8
    assert tr2.state.inflight is None
    # ...and both loops can continue from it
    tr2.run_pipelined(10, staleness=1, superstep=2)
    assert int(tr2.state.step) == 10
    tr2.run(12)
    assert int(tr2.state.step) == 12


# --------------------------------------------------------------------------
# overlap-aware cost model
# --------------------------------------------------------------------------

def test_overlap_cost_model_exposure():
    tb = [1.0, 2.0, 3.0]
    # no compute to hide under: everything exposed
    assert cm.exposed_bucket_times(tb, 0.0) == tb
    # infinite compute: fully hidden
    assert cm.exposed_bucket_times(tb, 100.0) == [0.0, 0.0, 0.0]
    # partial: the straddling bucket pays only its tail
    assert cm.exposed_bucket_times(tb, 2.5) == [0.0, 0.5, 3.0]
    assert sum(cm.exposed_bucket_times(tb, 2.5)) == pytest.approx(
        max(0.0, sum(tb) - 2.5))
    # pipelined step model: never slower than synchronous, equals
    # max(compute, comm) at staleness 1
    for tc in (0.0, 2.5, 10.0):
        t_sync = cm.t_step_overlapped(tc, tb, staleness=0)
        t_pipe = cm.t_step_overlapped(tc, tb, staleness=1)
        assert t_pipe <= t_sync
        assert t_pipe == pytest.approx(max(tc, sum(tb)) + 0.0)
    assert cm.t_step_overlapped(2.5, tb, staleness=0) == pytest.approx(8.5)


def test_plan_bucket_times_cover_every_bucket():
    from jax.sharding import PartitionSpec as P

    shapes = {"a": jax.ShapeDtypeStruct((1 << 15,), jnp.float32),
              "b": jax.ShapeDtypeStruct((64,), jnp.float32)}
    specs = {"a": P(), "b": P()}
    plan = comm.build_sync_plan(shapes, specs, SYNC, 8)
    tb = cm.plan_bucket_times(plan)
    assert len(tb) == plan.num_buckets
    assert all(t > 0 for t in tb)
