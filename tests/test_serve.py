"""Serve subsystem (DESIGN.md §8): continuous-batching scheduler,
slot decode engine, and the plan-driven sparse expert dispatch.

The two load-bearing invariants:

* continuous batching is INVISIBLE to a request: its tokens equal a
  per-request ``ServeEngine.generate`` greedy decode, token for token,
  whatever slots/arrivals/retirements happen around it;
* the sparse (row-stream) dispatch wire is EXACT: bit-identical to the
  dense psum reference on every lowering, as long as occupancy stays
  under the stream capacity (which the engine's guard enforces).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from functools import partial
from jax.sharding import PartitionSpec as P

from repro.compat import make_mesh, shard_map
from repro.comm import (
    CollectiveContext,
    build_serve_plan,
    exchange_activation,
    exchange_activation_spmd,
)
from repro.core import sparse_stream as ss
from repro.models.config import ModelConfig
from repro.models.model import build_model
from repro.models.moe import ServeDispatch, moe_apply, moe_apply_serve
from repro.runtime.adapt import AdaptConfig, AdaptiveController
from repro.serve import (
    ContinuousScheduler,
    ContinuousServeEngine,
    Request,
    ServeEngine,
    poisson_trace,
    truncate_at_eos,
)


# --------------------------------------------------------------------------
# Row streams + exchange parity
# --------------------------------------------------------------------------

def _row_sparse(p, t, d, nnz_rows, seed=0):
    rng = np.random.default_rng(seed)
    parts = np.zeros((p, t, d), np.float32)
    for s in range(p):
        for r in rng.choice(t, nnz_rows, replace=False):
            parts[s, r] = rng.standard_normal(d)
    return jnp.asarray(parts)


def test_row_stream_roundtrip_exact():
    x = np.asarray(_row_sparse(1, 16, 8, 3)[0])
    xj = jnp.asarray(x)
    st = ss.from_row_mask(xj, jnp.any(xj != 0, axis=1), cap=4)
    assert int(st.nnz) == 3
    back = ss.densify_rows(st, 16)
    np.testing.assert_array_equal(np.asarray(back), x)


def test_row_stream_overflow_clamps():
    # over capacity the round-trip is lossy — this is WHY the engine's
    # occupancy guard exists; the nnz count saturates at cap
    x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 4))
                    .astype(np.float32))
    st = ss.from_row_mask(x, jnp.ones((8,), bool), cap=4)
    assert int(st.nnz) == 4
    back = ss.densify_rows(st, 8)
    assert not np.array_equal(np.asarray(back), np.asarray(x))
    # the kept rows are the lowest indices, intact
    np.testing.assert_array_equal(np.asarray(back[:4]), np.asarray(x[:4]))


@pytest.mark.parametrize("p", [2, 8])
def test_exchange_spmd_sparse_equals_dense(p):
    parts = _row_sparse(p, 16, 8, 3)
    dense = exchange_activation_spmd(parts, "dense")
    sparse = exchange_activation_spmd(parts, "stream_gather@4")
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(sparse))


@pytest.mark.parametrize("p", [2, 8])
def test_exchange_manual_lowerings(p):
    """Manual lowerings of the activation exchange (DESIGN.md §8.2):

    * native: the real stream all-gather; its per-shard densify + sum is
      the same summation structure as the SPMD dense reference — bitwise
      equal at any p;
    * emulated (psum-only): the stream round-trip feeds the SAME psum as
      the dense path — bitwise equal to THAT reference at any p (psum's
      own reduction order may differ from the stacked sum's above p=2).
    """
    t, d = 16, 8
    parts = _row_sparse(p, t, d, 3)
    mesh = make_mesh((p,), ("model",))
    ref = np.asarray(exchange_activation_spmd(parts, "dense"))

    def native(x):
        coll = CollectiveContext("model", p, native=True)
        return exchange_activation(x[0], "stream_gather@4", coll=coll)[None]

    fn = shard_map(native, mesh=mesh, in_specs=P("model"),
                   out_specs=P("model"), axis_names={"model"})
    with mesh:
        out_native = np.asarray(jax.jit(fn)(parts))
    for s in range(p):
        np.testing.assert_array_equal(out_native[s], ref)

    def emul(x, rid, algorithm):
        coll = CollectiveContext("model", p, native=False, rank=rid[0])
        return exchange_activation(x[0], algorithm, coll=coll)[None]

    outs = {}
    for algorithm in ("dense", "stream_gather@4"):
        fe = shard_map(partial(emul, algorithm=algorithm), mesh=mesh,
                       in_specs=(P("model"), P("model")),
                       out_specs=P("model"), axis_names={"model"})
        with mesh:
            outs[algorithm] = np.asarray(
                jax.jit(fe)(parts, jnp.arange(p, dtype=jnp.int32)))
    np.testing.assert_array_equal(outs["stream_gather@4"], outs["dense"])
    np.testing.assert_allclose(outs["dense"][0], ref, rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------------
# Serve-time MoE dispatch
# --------------------------------------------------------------------------

def _moe_cfg(**kw):
    base = dict(name="t", family="moe", num_layers=2, d_model=64, num_heads=4,
                num_kv_heads=2, d_ff=128, vocab_size=256, dtype=jnp.float32,
                param_dtype=jnp.float32, max_seq_len=64, num_experts=4,
                experts_per_token=2, moe_d_ff=64, capacity_factor=4.0)
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def moe_model():
    cfg = _moe_cfg()
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def test_moe_serve_dispatch_masking_and_parity(moe_model):
    model, params = moe_model
    cfg = model.cfg
    lp = jax.tree.map(lambda a: a[0], params["blocks"])["moe"]
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((8, cfg.d_model)).astype(np.float32))
    act = jnp.zeros((8,), bool).at[0].set(True).at[3].set(True)

    def md(algorithm):
        return ServeDispatch(
            active=act,
            exchange=lambda parts: exchange_activation_spmd(parts, algorithm),
            p_shards=2)

    y_dense = moe_apply_serve(lp, cfg, x, md("dense"))
    y_sparse = moe_apply_serve(lp, cfg, x, md("stream_gather@4"))
    # sparse dispatch is bit-identical to the dense reference
    np.testing.assert_array_equal(np.asarray(y_dense), np.asarray(y_sparse))
    # inactive slots contribute and receive nothing through dispatch
    inactive = np.asarray(y_dense)[np.asarray(~act)]
    np.testing.assert_array_equal(inactive, np.zeros_like(inactive))
    # an active token's output is what a batch of just-itself computes
    y_solo = moe_apply(lp, cfg, x[0:1])
    np.testing.assert_allclose(np.asarray(y_dense)[0], np.asarray(y_solo)[0],
                               rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------------
# Scheduler unit tests
# --------------------------------------------------------------------------

def test_scheduler_lifecycle_and_fifo():
    reqs = [Request(rid=i, prompt=np.array([1, 2]), max_new_tokens=3,
                    arrival=a) for i, a in enumerate([0, 0, 5, 0])]
    sched = ContinuousScheduler(2, reqs, eos_id=99)
    admits = sched.admit_ready()
    assert [(i, r.rid) for i, r in admits] == [(0, 0), (1, 1)]  # FIFO
    for i, r in admits:
        sched.install(i, r, first_token=7)
    assert sched.active_count == 2 and not sched.admit_ready()
    # early EOS retires and frees the slot
    assert sched.record(0, 99) is True
    assert sched.completed[0].tolist() == [7, 99]
    # rid 3 (arrival 0) is admitted before rid 2 (arrival 5)
    admits = sched.admit_ready()
    assert [(i, r.rid) for i, r in admits] == [(0, 3)]
    sched.install(0, admits[0][1], first_token=1)
    # max_new_tokens retirement
    sched.record(1, 1)
    assert sched.record(1, 2) is True            # 3 tokens incl. install
    assert sched.completed[1].tolist() == [7, 1, 2]
    # idle skip jumps to the next arrival
    sched.record(0, 1), sched.record(0, 2)
    assert sched.active_count == 0 and sched.waiting
    sched.skip_to_next_arrival()
    assert sched.clock == 5.0
    assert [(i, r.rid) for i, r in sched.admit_ready()] == [(0, 2)]


def test_poisson_trace_deterministic():
    a = poisson_trace(16, rate=0.5, seed=7)
    b = poisson_trace(16, rate=0.5, seed=7)
    np.testing.assert_array_equal(a, b)
    assert (np.diff(a) > 0).all() and a.shape == (16,)
    assert not np.array_equal(a, poisson_trace(16, rate=0.5, seed=8))


def test_truncate_at_eos():
    t = np.array([3, 9, 4, 9, 5])
    assert truncate_at_eos(t, 9).tolist() == [3, 9]
    assert truncate_at_eos(t, 77).tolist() == t.tolist()
    assert truncate_at_eos(t, None).tolist() == t.tolist()


# --------------------------------------------------------------------------
# Continuous batching == per-request decode (token for token)
# --------------------------------------------------------------------------

def _requests(rng, specs):
    return [Request(rid=i, prompt=rng.integers(0, 256, L),
                    max_new_tokens=m, arrival=a)
            for i, (L, m, a) in enumerate(specs)]


def _references(model, mesh, params, reqs, cache_len, eos_id=None):
    eng = ServeEngine(model, mesh, params, cache_len=cache_len, batch_size=1)
    out = {}
    for r in reqs:
        toks = eng.generate(r.prompt[None], max_new_tokens=r.max_new_tokens)[0]
        out[r.rid] = truncate_at_eos(toks, eos_id)
    return out


def _assert_outputs_equal(got: dict, want: dict):
    assert set(got) == set(want)
    for rid in want:
        assert got[rid].tolist() == want[rid].tolist(), rid


def test_continuous_matches_per_request_dense_ragged_eos(mesh4x2):
    """Ragged prompts, staggered arrivals, early EOS: every request's
    continuous-batching output equals its own B=1 greedy decode."""
    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                      dtype=jnp.float32, param_dtype=jnp.float32,
                      max_seq_len=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = _requests(rng, [(3, 6, 0), (7, 4, 0), (5, 8, 0), (10, 5, 1),
                           (4, 7, 3), (6, 6, 8), (1, 4, 9)])
    plain = _references(model, mesh4x2, params, reqs, cache_len=32)
    # an EOS id that actually fires mid-stream for request 0
    eos = int(plain[0][2])
    want = {rid: truncate_at_eos(t, eos) for rid, t in plain.items()}
    eng = ContinuousServeEngine(model, mesh4x2, params, cache_len=32,
                                batch_size=4, eos_id=eos)
    res = eng.run(reqs)
    _assert_outputs_equal(res.outputs, want)
    assert res.tokens == sum(len(t) for t in want.values())


@pytest.fixture(scope="module")
def moe_serving(moe_model):
    """One MoE drain-shaped workload + its per-request references."""
    model, params = moe_model
    mesh = make_mesh((4, 2), ("data", "model"))
    rng = np.random.default_rng(1)
    # burst fills the slots (high occupancy), then short requests retire
    # and two long ones drain at low occupancy for many steps
    reqs = _requests(rng, [(4, 6, 0), (6, 5, 0), (3, 6, 0), (5, 4, 0),
                           (7, 5, 0), (4, 5, 0), (5, 22, 0), (6, 20, 1)])
    refs = _references(model, mesh, params, reqs, cache_len=32)
    return model, params, mesh, reqs, refs


def test_continuous_moe_dense_matches_per_request(moe_serving):
    model, params, mesh, reqs, refs = moe_serving
    eng = ContinuousServeEngine(model, mesh, params, cache_len=32,
                                batch_size=8, dispatch="dense")
    res = eng.run(reqs)
    _assert_outputs_equal(res.outputs, refs)


def test_continuous_moe_adaptive_exact_and_swaps(moe_serving):
    """The adaptive engine must (a) emit EXACTLY the dense reference's
    tokens, (b) log a telemetry-driven dense->stream swap during the
    drain, (c) put fewer modeled bytes on the wire than dense mode."""
    model, params, mesh, reqs, refs = moe_serving
    dense = ContinuousServeEngine(model, mesh, params, cache_len=32,
                                  batch_size=8, dispatch="dense")
    rd = dense.run(reqs)
    adap = ContinuousServeEngine(model, mesh, params, cache_len=32,
                                 batch_size=8, dispatch="adaptive")
    ra = adap.run(reqs)
    _assert_outputs_equal(ra.outputs, refs)
    _assert_outputs_equal(ra.outputs, rd.outputs)
    telem_swaps = [s for s in ra.swap_log if s["reason"] == "telemetry"]
    assert telem_swaps and "stream_gather" in telem_swaps[0]["signature"]
    assert ra.wire_bytes < rd.wire_bytes
    # the plan actually went sparse at low occupancy
    sparse_steps = [r for r in ra.step_log if "stream_gather" in r["signature"]]
    assert sparse_steps
    assert max(r["active"] for r in sparse_steps) <= 4


def test_occupancy_guard_forces_dense(moe_model):
    """A late burst that outgrows the stream capacity must force-demote
    to dense BEFORE any token is computed under an over-capacity stream
    — and the output must stay exact."""
    model, params = moe_model
    mesh = make_mesh((4, 2), ("data", "model"))
    rng = np.random.default_rng(2)
    reqs = _requests(rng, [(4, 18, 0), (5, 18, 0)] +
                     [(4, 8, 12 + i * 0.01) for i in range(6)])
    refs = _references(model, mesh, params, reqs, cache_len=32)
    adap = ContinuousServeEngine(model, mesh, params, cache_len=32,
                                 batch_size=8, dispatch="adaptive")
    res = adap.run(reqs)
    _assert_outputs_equal(res.outputs, refs)
    reasons = [s["reason"] for s in res.swap_log]
    assert "telemetry" in reasons          # drained to the stream first
    assert "occupancy-guard" in reasons    # burst forced it back to dense
    guard = [s for s in res.swap_log if s["reason"] == "occupancy-guard"][0]
    assert guard["signature"] == "act0=dense"


# --------------------------------------------------------------------------
# ServePlan + controller
# --------------------------------------------------------------------------

def test_serve_plan_selection_and_signature():
    plan = build_serve_plan(2, 16, 128, algorithm="dense")
    assert plan.signature() == "act0=dense"
    low = plan.replan({"act0": 2.0})
    assert low.signature() == "act0=stream_gather@4"
    assert low.version == plan.version + 1
    assert low.wire_bytes() < plan.wire_bytes()
    # high occupancy: cap would reach the token count -> dense
    high = low.replan({"act0": 14.0})
    assert high.signature() == "act0=dense"
    # capacity crossing is a forced switch, hysteresis may not veto it
    assert low.switch_forced("act0", "stream_gather@4", "dense", 4.0)
    assert not low.switch_forced("act0", "stream_gather@4", "dense", 3.0)
    assert not plan.switch_forced("act0", "dense", "stream_gather@4", 99.0)
    # explicit algorithm overrides (checkpoint-resume style)
    forced = plan.replan(algorithms={"act0": "stream_gather@8"})
    assert forced.signature() == "act0=stream_gather@8"
    assert forced.buckets[0].cap == 8


def test_adaptive_controller_drives_serve_plan():
    plan = build_serve_plan(2, 16, 128, algorithm="dense")
    ctrl = AdaptiveController(plan, cfg=AdaptConfig(
        window=2, patience=1, calibrate=False, pod_sparse=False))
    accepted = None
    for _ in range(4):
        accepted = ctrl.observe_step({"act0": 2.0}) or accepted
    assert accepted is not None
    assert accepted.signature() == "act0=stream_gather@4"
    # occupancy crossing the cap forces the way back up (no veto)
    back = None
    for _ in range(4):
        back = ctrl.observe_step({"act0": 14.0}) or back
    assert back is not None and back.signature() == "act0=dense"
    assert ctrl.swaps == 2


# --------------------------------------------------------------------------
# Per-slot positions in attention decode
# --------------------------------------------------------------------------

@pytest.mark.parametrize("window", [None, 6])
def test_vector_pos_attention_matches_scalar(window):
    from repro.models import layers as L
    from repro.models.layers import KVCache

    cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=32,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                      dtype=jnp.float32, param_dtype=jnp.float32,
                      max_seq_len=32, sliding_window=window)
    p = jax.tree.map(lambda a: a[0],
                     build_model(cfg).init(jax.random.PRNGKey(0))["blocks"])
    rng = np.random.default_rng(0)
    b, w = 4, 6 if window else 12
    x = jnp.asarray(rng.standard_normal((b, 1, 32)).astype(np.float32))
    kv = KVCache(
        jnp.asarray(rng.standard_normal((b, w, 2, 8)).astype(np.float32)),
        jnp.asarray(rng.standard_normal((b, w, 2, 8)).astype(np.float32)))
    pos = jnp.asarray([0, 3, 7, 11], jnp.int32)
    o_vec, kc_vec = L.attention_decode(p["attn"], cfg, x, kv, pos)
    # A B=1 call lowers the projections to XLA:CPU's matrix-vector dot,
    # whose summation order differs from the B=4 matrix-matrix dot, so it
    # can differ from row i of the batch in the last bits: at most
    # d_model * eps on these unit-scale activations.
    tol = cfg.d_model * np.finfo(np.float32).eps
    for i in range(b):
        pos_i = jnp.asarray(int(pos[i]), jnp.int32)
        # bitwise: the scalar path on the same batch shape, row i
        o_f, kc_f = L.attention_decode(p["attn"], cfg, x, kv, pos_i)
        np.testing.assert_array_equal(np.asarray(o_vec[i]), np.asarray(o_f[i]))
        np.testing.assert_array_equal(np.asarray(kc_vec.k[i]),
                                      np.asarray(kc_f.k[i]))
        np.testing.assert_array_equal(np.asarray(kc_vec.v[i]),
                                      np.asarray(kc_f.v[i]))
        kv1 = KVCache(kv.k[i:i + 1], kv.v[i:i + 1])
        o_s, kc_s = L.attention_decode(p["attn"], cfg, x[i:i + 1], kv1, pos_i)
        np.testing.assert_allclose(np.asarray(o_vec[i]), np.asarray(o_s[0]),
                                   rtol=0, atol=tol)
        np.testing.assert_allclose(np.asarray(kc_vec.k[i]),
                                   np.asarray(kc_s.k[0]), rtol=0, atol=tol)
