"""The reduction of the program's own marks (``bench/scope_reduce.py``) on
hand-made events and on a recorded trace, the readers that take it, and
``bench/run.py``'s traced path at a tiny size on the CPU."""
import importlib.util
import os
import random
from pathlib import Path

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import pytest  # noqa: E402

from bench import scope_reduce as sr  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
LOOP = "%while.7 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)"
BODY = "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop"
SORT = "%sort.4 = f32[64]{0} sort(f32[64]{0} %b), dimensions={0}"
COPY = "%copy.9 = s32[] copy(s32[] %c)"
SCOPE = {"while.7": "model", "fusion.2": "model", "sort.4": "sync/compress"}
READERS = {"model_ms": ("scope_incl_s", "model"),
           "compress_ms": ("scope_incl_s", "sync/compress"),
           "qsgd_ms": ("scope_incl_s", "sync/qsgd"),
           "update_ms": ("scope_incl_s", "update"),
           "host_step_ms": ("host_span_s", "train/run")}


def load(name: str, path: Path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name):
    return load(f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py").read


def scope_times(devices, op_scope, window):
    """``scope_times`` of the self times ``reduce_events`` finds."""
    op_s = tr.reduce_events({"devices": devices, "async": {}, "host": []},
                            window)["op_s"]
    return sr.scope_times(op_s, op_scope)


def test_scope_times_charge_innermost_self_time():
    devices = {
        # a loop (model) whose body fusion (model) runs 10-40 and 50-70,
        # a sort (compress) 120-130 and an XLA copy with no scope 140-150
        "/device:TPU:0": [(LOOP, 0, 100), (BODY, 10, 30), (BODY, 50, 20),
                          (SORT, 120, 10), (COPY, 140, 10)],
        "/device:TPU:1": [(SORT, 0, 40), (COPY, 200, 10)],
        "/device:TPU:2": []}
    out = scope_times(devices, SCOPE, (0, 300))
    # averaged over the two devices with events
    assert out["scope_s"]["model"] == pytest.approx(100e-9 / 2)
    assert out["scope_s"]["sync/compress"] == pytest.approx(50e-9 / 2)
    assert out["unscoped_s"] == pytest.approx(20e-9 / 2)
    assert list(out["scope_s"]) == ["model", "sync/compress"]
    # sync has no op of its own here, and holds sync/compress
    assert out["scope_incl_s"] == {"model": pytest.approx(100e-9 / 2),
                                   "sync": pytest.approx(50e-9 / 2),
                                   "sync/compress": pytest.approx(50e-9 / 2)}
    # an op wholly outside the window is left out, as op_s leaves it out
    out = scope_times(devices, SCOPE, (100, 300))
    assert "model" not in out["scope_s"]
    assert out["scope_s"]["sync/compress"] == pytest.approx(10e-9 / 2)


def test_nested_scope_leaves_model_ms_whole():
    """A later model that names ``model/x`` inside ``model`` moves time
    out of ``model``'s self time, and ``model_ms`` still reads the whole
    model."""
    inner = "%fusion.5 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop"
    devices = {"/device:TPU:0": [(LOOP, 0, 100), (BODY, 10, 30),
                                 (inner, 50, 20), (SORT, 120, 10)]}
    flat = scope_times(devices, {**SCOPE, "fusion.5": "model"}, (0, 300))
    nested = scope_times(devices, {**SCOPE, "fusion.5": "model/x"},
                            (0, 300))
    assert nested["scope_s"]["model"] == pytest.approx(80e-9)
    assert nested["scope_s"]["model/x"] == pytest.approx(20e-9)
    assert nested["scope_incl_s"]["model"] == pytest.approx(100e-9)
    facts = {"chips": 1, "steps": 1}
    assert reader("model_ms")(nested, facts) == pytest.approx(
        reader("model_ms")(flat, facts)) == pytest.approx(1e-4)
    assert sr.inclusive({"a/b/c": 1.0, "a": 2.0, "ab": 4.0}) == {
        "a": 3.0, "a/b": 1.0, "a/b/c": 1.0, "ab": 4.0}


def test_host_span_times_clip_to_window():
    spans = [("train/run", 0, 100), ("train/wait", 20, 50),
             ("train/run", 150, 100), ("train/wait", 260, 10),
             ("train/log", 400, 5)]
    out = sr.host_span_times(spans, (10, 200))
    assert out["host_span_n"] == {"train/run": 2, "train/wait": 1}
    assert out["host_span_s"]["train/run"] == pytest.approx(140e-9)
    assert out["host_span_s"]["train/wait"] == pytest.approx(50e-9)


def test_gaps_named_by_innermost_span():
    us = 1e3
    # two steps of the window loop: the device runs step 1 at 0-100 us, a
    # small copy at 540-545 (the next step's key) and step 2 at 590-745
    ev = {"devices": {"/device:TPU:0": [
        (BODY, 0, 100 * us), (COPY, 540 * us, 5 * us),
        (SORT, 590 * us, 155 * us)]}, "async": {},
        "host": [("step", 0, 450 * us), ("step", 450 * us, 295 * us),
                 ("batch", 470 * us, 70 * us)]}
    spans = [("train/run", 5 * us, 435 * us), ("train/wait", 10 * us, 90 * us),
             ("train/log", 100 * us, 180 * us),
             ("train/run", 455 * us, 290 * us),
             ("train/batch", 460 * us, 120 * us),
             ("train/dispatch", 580 * us, 20 * us),
             ("train/wait", 600 * us, 145 * us)]
    red = sr.reduce_with_program(ev, spans, SCOPE)
    # 100-540 us: train/log holds 180 us of it, train/run's own time 165,
    # the benchmark's batch 70, step's own 15, train/batch 10; 545-590 us:
    # train/batch 35, train/dispatch 10
    assert red["idle_gaps"] == [["train/log", pytest.approx(440e-6)],
                                ["train/batch", pytest.approx(45e-6)]]
    # the benchmark's rule gives the first gap to the enclosing step
    assert tr.attribute((100 * us, 540 * us), ev["host"] + spans) == "step"
    assert red["idle_by_span"] == {
        "train/log": pytest.approx(180e-6), "train/run": pytest.approx(165e-6),
        "batch": pytest.approx(70e-6), "train/batch": pytest.approx(45e-6),
        "step": pytest.approx(15e-6), "train/dispatch": pytest.approx(10e-6)}
    assert red["host_span_n"]["train/run"] == 2
    assert red["scope_s"] == {"sync/compress": pytest.approx(155e-6),
                              "model": pytest.approx(100e-6)}
    assert red["unscoped_s"] == pytest.approx(5e-6)
    # the keys trace_reduce gives are there as it gives them
    assert red["busy_s"] == pytest.approx(260e-6)
    assert red["window_s"] == pytest.approx(745e-6)


def test_innermost_time_leaves_uncovered_time_to_none():
    assert sr.innermost_time((0, 100), [("a", 20, 30), ("b", 0, 40)]) == {
        "b": pytest.approx(20e-9), "a": pytest.approx(30e-9),
        "none": pytest.approx(50e-9)}
    assert sr.innermost_time((0, 10), []) == {"none": pytest.approx(10e-9)}


@pytest.mark.parametrize("name", sorted(READERS))
def test_new_readers(name):
    read = reader(name)
    facts = {"chips": 1, "steps": 4}
    key, sub = READERS[name]
    assert read({"n_devices": 1}, facts) is None
    assert read({"n_devices": 1, key: {}}, facts) is None
    red = {"n_devices": 1, key: {sub: 0.2}}
    want = 50.0                            # 0.2 s over 4 steps
    if name == "host_step_ms":
        red[key]["train/wait"] = 0.12
        want = 20.0                        # (0.2 - 0.12) s over 4 steps
    assert read(red, facts) == pytest.approx(want)
    assert read(red, {"chips": 1, "steps": 0}) is None


def test_load_reads_the_trace_once(monkeypatch):
    """One read of the file gives the benchmark's spans and the program's
    apart, and the device events as ``trace_reduce.load`` gives them."""
    import jax

    path = str(BENCH / "testdata" / "trace_1chip.xplane.pb")
    want = tr.load(path)
    reads = []
    from_file = jax.profiler.ProfileData.from_file
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        lambda p: reads.append(p) or from_file(p))
    ev, spans = sr.load(path)
    assert reads == [path]
    assert ev == want and spans == []


def test_idle_names_match_every_span():
    """Gaps named among only the spans near each gap read as they do
    among all the spans."""
    rng = random.Random(2**31 + 5)
    names = ("step", "batch", "train/run", "train/wait", "train/log")
    for _ in range(20):
        evs, t = [], 0.0
        for _ in range(60):
            t += rng.choice((0.0, 500.0, 3e3, 2e4))
            d = rng.uniform(1e3, 5e4)
            evs.append((BODY, t, d))
            t += d
        spans = [(rng.choice(names), s, rng.uniform(1.0, 2e5))
                 for s in sorted(rng.uniform(-1e5, t) for _ in range(40))]
        out = sr.idle_names({"/device:TPU:0": evs}, spans, (0.0, t))
        by_span: dict = {}
        for g in tr.gaps(tr.union((s, s + d) for _, s, d in evs), 0.0, t):
            for n, secs in sr.innermost_time(g, spans).items():
                by_span[n] = by_span.get(n, 0.0) + secs
        assert out["idle_by_span"] == pytest.approx(by_span)


def test_traced_run_on_cpu(monkeypatch):
    """``bench/run.py``'s traced path at a tiny size: the step compiles
    once, for its scope map, and that compile serves every call; the
    program's spans give ``host_step_ms``; the run is correct. (The CPU
    trace has no TPU plane, so no device metric is read.)"""
    import time

    import jax

    import bench.peaks
    from bench import run
    from bench.tests.test_bench_faults import tiny_cell

    monkeypatch.setattr(bench.peaks, "peak",
                        lambda kind: {"bf16_flops_per_s": 1.0})
    compiled = []
    on = run.CompileLog._on

    def count(self, event, duration, **kw):
        on(self, event, duration, **kw)
        if event == self.EVENT:
            compiled.append(str(kw.get("fun_name")))

    monkeypatch.setattr(run.CompileLog, "_on", count)
    texts = []
    step_text = run.step_text
    monkeypatch.setattr(run, "step_text",
                        lambda *a: texts.append(step_text(*a)) or texts[-1])
    wl, cfgf = tiny_cell("mamba2", 1)
    res = run.run_cell("tiny-traced", wl, cfgf, 2**31 + 23, 0.0, True,
                       jax.devices()[:1], time.perf_counter())
    assert res["correct"], res["checks"]
    assert len(texts) == 1 and "op_name=" in texts[0]
    assert compiled.count("jit(stepped)") == 1, compiled
    assert res["metrics"]["host_step_ms"]["value"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
