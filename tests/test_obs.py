"""Observability layer (DESIGN.md §10): tracer, metrics registry, drift
auditor, and their integration with the pipelined driver and the serve
engine.

The two load-bearing invariants:

* obs OFF is free: the driver's loop is byte-identical, every span a
  shared no-op context manager;
* obs ON adds NO sync points: retire's ``block_until_ready`` stays the
  only one (counted under a monkeypatch), the span tree is well-formed
  Chrome-trace JSON, and every span also lands under its own name on the
  ``jax.profiler`` trace's host plane.
"""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs as obs_mod
from repro.compat import make_mesh
from repro.core.compressor import SyncConfig
from repro.data.pipeline import DataConfig, synthetic_batch
from repro.models.config import ModelConfig
from repro.models.model import build_model
from repro.obs import (
    DriftAuditor,
    MetricsRegistry,
    NULL_TRACER,
    Observability,
    Tracer,
    audit_sync_plan,
    record_bucket_telemetry,
    validate_span_tree,
)
from repro.optim.optimizers import OptimizerConfig
from repro.optim.schedule import ScheduleConfig
from repro.runtime import driver as rt_driver
from repro.runtime import pipeline as rt_pipeline
from repro.serve import ContinuousServeEngine, Request
from repro.train.state import TrainConfig
from repro.train.train_step import init_state

MODEL_CFG = ModelConfig(name="obs", family="dense", num_layers=2, d_model=64,
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


# --------------------------------------------------------------------------
# Tracer units
# --------------------------------------------------------------------------

def test_null_tracer_is_shared_noop():
    from repro.obs.trace import _NULL_SPAN

    assert not NULL_TRACER.enabled
    # the hot-path contract: a disabled span() is the SAME object every
    # call (no allocation), and recording is a no-op
    assert NULL_TRACER.span("a") is NULL_TRACER.span("b") is _NULL_SPAN
    NULL_TRACER.instant("x")
    assert NULL_TRACER.events == []


def test_span_tree_nesting_and_export(tmp_path):
    tr = Tracer()
    with tr.span("outer", step=1):
        with tr.span("inner/a"):
            pass
        with tr.span("inner/b"):
            pass
    tr.instant("marker")
    tr.counter("occupancy", active=3)
    assert validate_span_tree(tr.events) == []
    names = [e["name"] for e in tr.events if e["ph"] == "X"]
    # spans record on exit, so children precede the parent in the list
    assert names == ["inner/a", "inner/b", "outer"]
    path = tr.export(str(tmp_path / "t.json"), meta={"run": "test"})
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["run"] == "test"
    assert len(doc["traceEvents"]) == len(tr.events)
    outer = next(e for e in doc["traceEvents"] if e["name"] == "outer")
    assert outer["args"]["step"] == 1 and outer["dur"] >= 0


def test_validate_span_tree_catches_partial_overlap():
    evs = [
        {"name": "a", "ph": "X", "ts": 0.0, "dur": 100.0, "pid": 1, "tid": 1},
        {"name": "b", "ph": "X", "ts": 50.0, "dur": 100.0, "pid": 1, "tid": 1},
    ]
    bad = validate_span_tree(evs)
    assert len(bad) == 1 and "partially overlaps" in bad[0]
    # same intervals on DIFFERENT tracks: fine
    evs[1]["tid"] = 2
    assert validate_span_tree(evs) == []


# --------------------------------------------------------------------------
# Metrics registry units
# --------------------------------------------------------------------------

def test_registry_kinds_and_jsonl_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2)
    reg.gauge("g").set(1.5)
    for v in [1.0, 2.0, 3.0, 4.0]:
        reg.histogram("h").observe(v)
    reg.series("s").append((1, "x"))
    reg.event("ev/one", step=3, signature="sig")
    # get-or-create returns the same object; kind conflicts raise
    assert reg.counter("c").value == 3
    with pytest.raises(TypeError):
        reg.gauge("c")

    path = reg.dump_jsonl(str(tmp_path / "m.jsonl"), meta={"who": "test"})
    lines = [json.loads(ln) for ln in open(path)]
    head = lines[0]
    assert head["kind"] == "header" and head["schema_version"] == 2
    assert head["meta"]["who"] == "test"
    by = {(ln["kind"], ln.get("name")): ln for ln in lines[1:]}
    assert by[("counter", "c")]["value"] == 3
    assert by[("gauge", "g")]["value"] == 1.5
    h = by[("histogram", "h")]
    assert h["count"] == 4 and h["min"] == 1.0 and h["max"] == 4.0
    assert by[("series", "s")]["values"] == [[1, "x"]]
    evs = [ln for ln in lines if ln["kind"] == "event"]
    assert evs[0]["event"] == "ev/one" and evs[0]["step"] == 3
    assert "summary" not in reg.summary()  # smoke: renders without raising


def test_disabled_registry_series_still_back_logs():
    """DriverLog's public fields are Series views — they must work (as
    plain lists) even when the registry is disabled, while events stay
    off."""
    reg = MetricsRegistry(enabled=False)
    data = reg.series("train/loss").data
    data.append(1.0)
    assert reg.series("train/loss").data == [1.0]
    reg.event("nope", x=1)
    assert reg.events == []


def test_record_bucket_telemetry_shapes():
    reg = MetricsRegistry()
    telem = {"b0": np.array([[3, 96.0], [5, 160.0]]),
             "scalar": np.array([1.0])}  # wrong shape: ignored
    record_bucket_telemetry(reg, telem)
    assert reg.histogram("bucket/b0/nnz").values == [3.0, 5.0]
    assert reg.histogram("bucket/b0/wire_bytes").values == [96.0, 160.0]
    assert "bucket/scalar/nnz" not in reg.metrics


def test_histogram_percentiles():
    reg = MetricsRegistry()
    reg.histogram("h").observe_many(np.arange(1, 101, dtype=np.float64))
    s = reg.histogram("h").snapshot()
    assert s["p50"] == pytest.approx(50.5)
    assert s["p99"] == pytest.approx(99.01)
    assert reg.histogram("h").percentile(90) == pytest.approx(90.1)


def test_histogram_percentiles_empty_and_single_sample():
    reg = MetricsRegistry()
    h = reg.histogram("empty")
    # empty: NaN percentile (never a crash), count-0 snapshot, renderable
    assert np.isnan(h.percentile(99))
    assert h.snapshot() == {"count": 0}
    assert h.brief() == "empty"
    # single sample: every percentile IS that sample
    one = reg.histogram("one")
    one.observe(7.5)
    s = one.snapshot()
    assert s["count"] == 1
    for q in ("p50", "p90", "p99", "min", "max", "mean"):
        assert s[q] == 7.5
    assert one.percentile(0) == one.percentile(100) == 7.5


def test_series_view_survives_registry_disabled_mid_run():
    """DriverLog holds Series ``.data`` views for the run's lifetime; a
    registry toggled off mid-run must keep those views alive (same list,
    appends land) while the event log goes quiet."""
    reg = MetricsRegistry()
    view = reg.series("train/loss").data
    view.append(1.0)
    reg.event("before", x=1)
    reg.enabled = False
    # same backing object, not a fresh one
    assert reg.series("train/loss") is reg.series("train/loss")
    assert reg.series("train/loss").data is view
    view.append(2.0)
    reg.series("train/loss").append(3.0)
    assert view == [1.0, 2.0, 3.0]
    reg.event("after", x=2)   # dropped: registry is off
    assert [e["event"] for e in reg.events] == ["before"]
    # re-enable: the history was never lost
    reg.enabled = True
    reg.event("resumed")
    assert len(reg.events) == 2 and reg.series("train/loss").data is view


def test_validate_span_tree_out_of_order_events():
    """Spans record on EXIT, so the event list is naturally child-first
    and may interleave arbitrarily across tracks — the validator must
    sort per track, not trust input order."""
    nested = [
        {"name": "root", "ph": "X", "ts": 0.0, "dur": 100.0,
         "pid": 1, "tid": 1},
        {"name": "mid", "ph": "X", "ts": 10.0, "dur": 50.0,
         "pid": 1, "tid": 1},
        {"name": "leaf", "ph": "X", "ts": 20.0, "dur": 10.0,
         "pid": 1, "tid": 1},
        {"name": "tail", "ph": "X", "ts": 70.0, "dur": 20.0,
         "pid": 1, "tid": 1},
    ]
    # every permutation of a well-formed tree validates clean
    import itertools

    for perm in itertools.permutations(nested):
        assert validate_span_tree(list(perm)) == []
    # an overlap is caught regardless of where it sits in the list
    bad_ev = {"name": "ovl", "ph": "X", "ts": 45.0, "dur": 20.0,
              "pid": 1, "tid": 1}
    for pos in range(len(nested) + 1):
        evs = nested[:pos] + [bad_ev] + nested[pos:]
        bad = validate_span_tree(evs)
        assert len(bad) == 1 and "ovl" in bad[0]
    # same-ts siblings: longer span is the parent (tiebreak), zero-dur
    # markers nest anywhere, non-X events are ignored
    twins = [
        {"name": "p", "ph": "X", "ts": 0.0, "dur": 100.0, "pid": 1, "tid": 1},
        {"name": "c", "ph": "X", "ts": 0.0, "dur": 40.0, "pid": 1, "tid": 1},
        {"name": "dot", "ph": "X", "ts": 99.9, "dur": 0.0, "pid": 1, "tid": 1},
        {"name": "i", "ph": "i", "ts": 1e9, "pid": 1, "tid": 1},
    ]
    assert validate_span_tree(twins) == []


# --------------------------------------------------------------------------
# Drift auditor units
# --------------------------------------------------------------------------

def test_drift_auditor_flags_drifted_algorithm():
    aud = DriftAuditor(flag_ratio=3.0)
    for i in range(3):
        aud.record("good_alg", f"b{i}", 1e-3, 1.1e-3)
        aud.record("bad_alg", f"b{i}", 1e-3, 1e-2)   # 10x drift
    stats = aud.per_algorithm()
    assert not stats["good_alg"]["flagged"]
    assert stats["bad_alg"]["flagged"]
    assert stats["bad_alg"]["median_ratio"] == pytest.approx(10.0)
    assert aud.flagged_algorithms() == ["bad_alg"]
    # overall hint: median over all 6 samples
    assert aud.net_scale_hint() == pytest.approx(np.median([1.1] * 3 + [10.0] * 3))
    rep = aud.report()
    assert rep["samples"] == 6 and rep["flagged"] == ["bad_alg"]
    # emit mirrors into the registry as events + gauge
    reg = MetricsRegistry()
    aud.emit(reg)
    assert len(reg.events_named("audit/algorithm_residual")) == 2
    assert reg.gauge("audit/net_scale_hint").value is not None
    assert "bad_alg" in aud.summary() and "DRIFT" in aud.summary()


# --------------------------------------------------------------------------
# Driver integration: no extra syncs, well-formed trace, bounded overhead
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh8x1():
    return make_mesh((8, 1), ("data", "model"))


@pytest.fixture(scope="module")
def pipelined(mesh8x1):
    model = build_model(MODEL_CFG)
    with mesh8x1:
        fn, _, plan = rt_pipeline.build_superstep(
            model, TCFG, mesh8x1, staleness=1, steps=2)
    return model, fn, plan


def _drive(mesh, model, fn, plan, n=8, obs=None):
    with mesh:
        state, _ = init_state(model, TCFG, mesh)
        state = rt_pipeline.attach_inflight(state, plan, mesh)
        state, log = rt_driver.run_pipelined(
            fn, state, start_step=0, num_steps=n,
            batch_fn=lambda s: synthetic_batch(DCFG, s),
            key_fn=lambda s: jax.random.fold_in(KEY, s),
            cfg=rt_driver.DriverConfig(depth=2, prefetch=2,
                                       steps_per_unit=2),
            obs=obs)
    return state, log


def test_driver_obs_adds_no_sync_points(mesh8x1, pipelined, monkeypatch):
    """Retire's ``block_until_ready`` is the ONLY sync point — the same
    count with observability off and fully on (trace+metrics)."""
    model, fn, plan = pipelined
    real = jax.block_until_ready
    counts = {"n": 0}

    def counting(x):
        counts["n"] += 1
        return real(x)

    def run(obs):
        counts["n"] = 0
        monkeypatch.setattr(jax, "block_until_ready", counting)
        try:
            _drive(mesh8x1, model, fn, plan, n=8, obs=obs)
        finally:
            monkeypatch.setattr(jax, "block_until_ready", real)
        return counts["n"]

    off = run(obs_mod.Observability())            # all-off handle
    on = run(obs_mod.configure(trace=True, metrics=True,
                               set_as_default=False))
    assert off == on == 4        # one retire per 2-step unit, 8 steps


def test_driver_trace_well_formed_and_metrics_backed(mesh8x1, pipelined,
                                                     tmp_path):
    model, fn, plan = pipelined
    obs = obs_mod.configure(trace=True, metrics=True, set_as_default=False)
    n = 8
    state, log = _drive(mesh8x1, model, fn, plan, n=n, obs=obs)
    assert int(state.step) == n

    # the DriverLog's public lists ARE registry series views
    assert log.losses is obs.metrics.series("train/loss").data
    assert len(log.losses) == n == len(log.step_times)
    assert obs.metrics.histogram("driver/retire_wall_s").snapshot()["count"] == 4

    # well-formed span tree with the driver's host spans present
    assert validate_span_tree(obs.tracer.events) == []
    names = {e["name"] for e in obs.tracer.events if e["ph"] == "X"}
    assert {"driver/dispatch", "driver/retire"} <= names

    # the export is loadable Chrome-trace JSON
    doc = json.load(open(obs.tracer.export(str(tmp_path / "t.json"))))
    assert len(doc["traceEvents"]) == len(obs.tracer.events)


def test_driver_obs_overhead_bounded(mesh8x1, pipelined):
    """Tracing budget: what an enabled tracer adds to a driven step stays
    under 5% of the step. Priced as the events a traced, metrics-on
    driver run records per step times the cost of one enabled span (best
    of 5 tight loops, into a tracer already holding a million events, as
    a long run's does), against that run's median step time. A span that
    allocated in proportion to the trace, or a driver that recorded
    events per bucket or per array, blows the budget; the load of a
    shared machine moves both sides alike, since no two whole-run wall
    times are compared.
    (Sync points are pinned by test_driver_obs_adds_no_sync_points.)"""
    model, fn, plan = pipelined
    obs = obs_mod.configure(trace=True, metrics=True, set_as_default=False)
    n = 8
    _, log = _drive(mesh8x1, model, fn, plan, n=n, obs=obs)
    events_per_step = len(obs.tracer.events) / n
    step_s = float(np.median(log.step_times))

    tr = Tracer()
    tr.events = [{}] * 1_000_000

    def span_s(reps=5000):
        t0 = time.perf_counter()
        for i in range(reps):
            with tr.span("driver/dispatch", step=i, k=2):
                pass
        return (time.perf_counter() - t0) / reps

    per_span = min(span_s() for _ in range(5))
    assert events_per_step * per_span <= 0.05 * step_s, (
        events_per_step, per_span, step_s)


# --------------------------------------------------------------------------
# Program spans on the profiler's clock; the train step's named scopes
# --------------------------------------------------------------------------

def test_enabled_span_lands_on_profiler_host_plane(tmp_path):
    """An enabled span is a TraceAnnotation under its exact name, its
    args as metadata; the OFF handle's span writes nothing."""
    import glob

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_mod.configure(trace=True, set_as_default=False).span(
                "obs/probe", step=7):
            jnp.ones(4).block_until_ready()
        with obs_mod.OFF.span("obs/off_probe"):
            pass
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    host = [e for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]
    probes = [e for e in host if e.name == "obs/probe"]
    assert len(probes) == 1 and probes[0].duration_ns > 0
    assert ("step", 7) in list(probes[0].stats)
    assert not [e for e in host if "off_probe" in e.name]


@pytest.fixture(scope="module")
def traced_trainer(tmp_path_factory):
    """A tiny sparcml Trainer (top-k, 4-bit QSGD, ZeRO-1, 2 microbatches)
    run 4 steps with tracing on and a checkpoint every 2 steps."""
    import dataclasses

    from repro.train.trainer import Trainer

    mesh = make_mesh((8, 1), ("data", "model"))
    tcfg = dataclasses.replace(
        TCFG, microbatches=2,
        sync=dataclasses.replace(SYNC, qsgd_bits=4, qsgd_bucket=128))
    ob = obs_mod.configure(trace=True, set_as_default=False)
    tr = Trainer(build_model(MODEL_CFG), tcfg, mesh,
                 DataConfig(global_batch=16, seq_len=32, vocab_size=256),
                 ckpt_dir=str(tmp_path_factory.mktemp("ckpt")),
                 ckpt_every=2, obs=ob)
    tr.run(4)
    return tr


def test_trainer_run_emits_train_spans(traced_trainer):
    events = traced_trainer.obs.tracer.events
    assert validate_span_tree(events) == []
    spans = [e for e in events if e["ph"] == "X"]
    count = {n: sum(e["name"] == n for e in spans)
             for n in {e["name"] for e in spans}}
    # one run call; 4 steps of batch/dispatch/wait/log; checkpoints after
    # steps 2 and 4 and the closing one
    assert count == {"train/run": 1, "train/batch": 4, "train/dispatch": 4,
                     "train/wait": 4, "train/log": 4, "train/checkpoint": 3}
    run, = (e for e in spans if e["name"] == "train/run")
    assert all(run["ts"] <= e["ts"] and
               e["ts"] + e["dur"] <= run["ts"] + run["dur"] for e in spans)


def test_op_scopes_cover_step_parts(traced_trainer):
    from repro.obs import scopes

    tr = traced_trainer
    batch = jax.tree.map(jnp.asarray, synthetic_batch(tr.data_cfg, 0))
    with tr.mesh:
        text = tr.step_fn.lower(tr.state, batch, KEY).compile().as_text()
    found = set(scopes.op_scopes(text).values())
    assert {scopes.MODEL, scopes.COMPRESS, scopes.QSGD,
            scopes.UPDATE} <= found <= set(scopes.SCOPES)


def test_scope_of_names_innermost_scope():
    from repro.obs.scopes import op_scopes, scope, scope_of

    assert scope_of("jit(stepped)/shard_map/sync/compress/top_k") == \
        "sync/compress"
    assert scope_of("jit(stepped)/shard_map/sync/all_to_all") == "sync"
    # the backward pass wraps the scope in transforms
    assert scope_of("jit(f)/model/while/body/transpose(jvp(model))/mul") \
        == "model"
    assert scope_of("jit(f)/update/sync") == "update"
    assert scope_of("jit(f)/shard_map/add") is None
    # the primitive (last component) never names a scope
    assert scope_of("jit(f)/update") is None
    text = """\
%body.1 (p.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p.1 = (s32[], f32[8]{0}) parameter(0)
  %dot.5 = f32[8]{0} dot(f32[8]{0} %p.1, f32[8]{0} %p.1)
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%p.1, %dot.5)
}

ENTRY %main.9 (p.0: f32[8]) -> f32[64] {
  %p.0 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %while.3 = (s32[], f32[8]{0}) while(%p.0), condition=%cond.1, \
body=%body.1, metadata={op_name="jit(s)/model/while"}
  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %p.0), kind=kLoop, \
metadata={op_name="jit(s)/update/sub" source_line=3}
  %copy.9 = f32[8]{0} copy(%fusion.3)
  %copy.10 = f32[8]{0} copy(%p.0)
  ROOT %sort.4 = f32[64]{0} sort(%copy.9), dimensions={0}, \
metadata={op_name="jit(s)/sync/compress/jit(sort)/sort"}
}
"""
    # the loop body's ops take the loop's scope, the inserted copy its
    # operand's; the parameter and its copy have none
    assert op_scopes(text) == {
        "p.1": "model", "dot.5": "model", "tuple.2": "model",
        "while.3": "model", "fusion.3": "update", "copy.9": "update",
        "sort.4": "sync/compress"}
    with pytest.raises(ValueError):
        scope("sync/topk")


def test_record_step_straggler_watchdog():
    reg = MetricsRegistry()
    log = rt_driver.DriverLog(registry=reg)
    for i in range(10):
        rt_driver.record_step(log, i, 0.01, 1.0, straggler_factor=3.0)
    assert log.straggler_events == []
    rt_driver.record_step(log, 10, 1.0, 1.0, straggler_factor=3.0)
    assert len(log.straggler_events) == 1
    step, dt, med = log.straggler_events[0]
    assert step == 10 and dt == 1.0 and med == pytest.approx(0.01)
    assert reg.counter("driver/stragglers").value == 1
    assert reg.gauge("driver/straggler_median_s").value == pytest.approx(0.01)
    assert len(reg.events_named("driver/straggler")) == 1
    # restarts round-trips through its backing counter
    log.restarts += 1
    assert log.restarts == 1 == reg.counter("driver/restarts").value


def test_driverlog_standalone_works_like_plain_lists():
    log = rt_driver.DriverLog()
    log.losses.append(2.5)
    log.step_times.append(0.1)
    log.plan_swaps.append((3, "sig"))
    assert log.losses[-1] == 2.5 and log.plan_swaps[0][1] == "sig"
    assert log.restarts == 0


# --------------------------------------------------------------------------
# Drift audit over a real plan
# --------------------------------------------------------------------------

def test_audit_sync_plan_probes_buckets(mesh8x1, pipelined):
    model, fn, plan = pipelined
    reg = MetricsRegistry()
    aud = audit_sync_plan(plan, mesh8x1, axis_name="data",
                          reps=1, registry=reg)
    assert len(aud) >= 1
    stats = aud.per_algorithm()
    for st in stats.values():
        assert st["predicted_total_s"] > 0
        assert st["measured_total_s"] > 0
        assert np.isfinite(st["median_ratio"])
    # the join was mirrored into the registry
    assert len(reg.events_named("audit/algorithm_residual")) == len(stats)


# --------------------------------------------------------------------------
# Serve latency percentiles
# --------------------------------------------------------------------------

def test_serve_latency_percentiles_deterministic():
    """Latency stats are in decode-step units on the scheduler's
    deterministic clock: two identical runs on the same fixed trace give
    IDENTICAL percentile dicts, and ttft == queue_delay (the prefill
    argmax IS the first token, landed at admission)."""
    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                      dtype=jnp.float32, param_dtype=jnp.float32,
                      max_seq_len=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    mesh = make_mesh((4, 2), ("data", "model"))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, 256, L),
                    max_new_tokens=m, arrival=a)
            for i, (L, m, a) in enumerate(
                [(3, 6, 0), (7, 4, 0), (5, 8, 1), (4, 7, 3), (6, 6, 8)])]

    def run():
        eng = ContinuousServeEngine(model, mesh, params, cache_len=32,
                                    batch_size=4)
        return eng.run(reqs)

    r1, r2 = run(), run()
    assert r1.latency and r1.latency == r2.latency
    for metric in ("queue_delay", "ttft", "tpot", "e2e"):
        assert set(r1.latency[metric]) == {"p50", "p90", "p99", "mean"}
    assert r1.latency["ttft"] == r1.latency["queue_delay"]
    # e2e >= queue delay for every percentile (decode takes steps)
    assert r1.latency["e2e"]["p99"] >= r1.latency["queue_delay"]["p99"]


def test_serve_obs_records_lifecycle(tmp_path):
    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                      dtype=jnp.float32, param_dtype=jnp.float32,
                      max_seq_len=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    mesh = make_mesh((4, 2), ("data", "model"))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, 256, 4),
                    max_new_tokens=4, arrival=float(i // 2))
            for i in range(4)]
    obs = obs_mod.configure(trace=True, metrics=True, set_as_default=False)
    eng = ContinuousServeEngine(model, mesh, params, cache_len=32,
                                batch_size=2, obs=obs)
    res = eng.run(reqs)
    assert len(res.outputs) == 4
    assert validate_span_tree(obs.tracer.events) == []
    names = {e["name"] for e in obs.tracer.events if e["ph"] == "X"}
    assert {"serve/admit", "serve/decode_step"} <= names
    for h in ("serve/occupancy", "serve/queue_depth",
              "serve/ttft_steps", "serve/e2e_steps"):
        assert obs.metrics.histogram(h).snapshot()["count"] > 0
    assert obs.metrics.gauge("serve/tok_per_s").value > 0
    out = obs.export(trace_path=str(tmp_path / "t.json"),
                     metrics_path=str(tmp_path / "m.jsonl"))
    assert os.path.exists(out["trace"]) and os.path.exists(out["metrics"])


# --------------------------------------------------------------------------
# bench-regress compare logic
# --------------------------------------------------------------------------

def _regress():
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import regress
    return regress


def test_regress_loads_both_schemas_and_compares(tmp_path):
    regress = _regress()
    fresh, base = tmp_path / "fresh", tmp_path / "base"
    fresh.mkdir(), base.mkdir()
    # baseline in the OLD v1 flat-list format; fresh in v2
    (base / "BENCH_bench_adapt.json").write_text(json.dumps(
        [{"name": "adapt_drift_adaptive", "us_per_call": 100.0,
          "derived": ""}]))
    (fresh / "BENCH_bench_adapt.json").write_text(json.dumps(
        {"schema_version": 2, "meta": {},
         "rows": [{"name": "adapt_drift_adaptive", "us_per_call": 110.0,
                   "derived": ""}]}))
    (base / "BENCH_bench_serve.json").write_text(json.dumps(
        [{"name": "serve_continuous", "us_per_call": 1.0,
          "derived": "tok_per_s=100.0,decode_steps=50"}]))
    (fresh / "BENCH_bench_serve.json").write_text(json.dumps(
        [{"name": "serve_continuous", "us_per_call": 1.0,
          "derived": "tok_per_s=60.0,decode_steps=50"}]))

    cells = regress.headline_cells(str(fresh), str(base))
    by = {c["label"]: c for c in cells}
    # per-cell bands attached from the built-in table
    assert by["adapt_drift_adaptive.us_per_call"]["tol"] == 0.25
    assert by["serve_continuous.tok_per_s"]["tol"] == 0.35
    # adapt: 10% slower (lower-better) — inside its 25% band
    # serve: 40% fewer tok/s (higher-better) — beyond its 35% band,
    # even under a flat fallback wide enough to let it pass
    bad = regress.compare(cells, tol=0.5)
    assert by["adapt_drift_adaptive.us_per_call"] not in bad
    assert by["serve_continuous.tok_per_s"] in bad
    assert by["serve_continuous.tok_per_s"]["regression"] == pytest.approx(0.4)
    # cells without their own band fall back to the flat tol
    for c in cells:
        c.pop("tol", None)
    assert regress.compare(cells, tol=0.5) == []
    assert by["serve_continuous.tok_per_s"] in regress.compare(cells,
                                                               tol=0.25)


def test_regress_per_cell_tolerance_from_baseline_meta(tmp_path):
    """A band committed in the baseline file's meta.tolerances overrides
    the built-in table, and --update-style merges preserve it."""
    regress = _regress()
    fresh, base = tmp_path / "fresh", tmp_path / "base"
    fresh.mkdir(), base.mkdir()
    (base / "BENCH_bench_serve.json").write_text(json.dumps(
        {"schema_version": 2,
         "meta": {"tolerances": {"serve_continuous.tok_per_s": 0.6}},
         "rows": [{"name": "serve_continuous", "us_per_call": 1.0,
                   "derived": "tok_per_s=100.0"}]}))
    (fresh / "BENCH_bench_serve.json").write_text(json.dumps(
        [{"name": "serve_continuous", "us_per_call": 1.0,
          "derived": "tok_per_s=60.0"}]))
    cells = regress.headline_cells(str(fresh), str(base))
    assert cells[0]["tol"] == 0.6
    # 40% regression sits inside the committed 60% band
    assert regress.compare(cells, tol=0.25) == []
    # wire_bytes cells default to the tight analytic band
    assert regress.cell_tol("portfolio_x_d01.wire_bytes", {}) == \
        regress.WIRE_BYTES_TOL


def test_regress_parse_derived_and_improvements():
    regress = _regress()
    d = regress.parse_derived("tok_per_s=61.4,continuous_wins=True,n=3")
    assert d == {"tok_per_s": "61.4", "continuous_wins": "True", "n": "3"}
    # improvements never fail, in either direction convention
    cells = [
        {"label": "lower", "fresh": 50.0, "baseline": 100.0,
         "higher_better": False},
        {"label": "higher", "fresh": 200.0, "baseline": 100.0,
         "higher_better": True},
    ]
    assert regress.compare(cells, tol=0.25) == []
    assert cells[0]["regression"] == pytest.approx(-0.5)
