"""The trace reduction, on a trace recorded on one TPU v5e chip
(``bench/testdata/trace_1chip.xplane.pb``, written by
``bench/tools/record_testdata.py``) and on hand-made events with
collectives."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parents[1] / "testdata"


@pytest.fixture(scope="module")
def chip_trace():
    return tr.load(str(DATA / "trace_1chip.xplane.pb"))


def test_recorded_trace_events(chip_trace):
    # 3 steps of 9 XLA ops each (an add, a prefetch pair, six fusions),
    # one chip, and the 9 host spans batch/dispatch/wait x 3
    evs = chip_trace["devices"]["/device:TPU:0"]
    assert len(evs) == 27
    assert [n for n, _, _ in chip_trace["host"]] == \
        ["batch", "dispatch", "wait"] * 3


def test_recorded_trace_busy_idle_and_gaps(chip_trace):
    red = tr.reduce_events(chip_trace)
    # hand count from the 27 events: first op starts at 141,416,283 ns,
    # the last ends at 194,487,792 + 13,322 ns; the ops do not overlap
    # and their durations sum to 269,547 ns
    assert red["window_s"] == pytest.approx(53.084831e-3, abs=1e-12)
    assert red["busy_s"] == pytest.approx(269.547e-6, abs=1e-12)
    assert 100 * (1 - red["busy_s"] / red["window_s"]) == \
        pytest.approx(99.4922, abs=1e-4)
    assert red["collective_s"] == 0 and red["exposed_collective_s"] == 0
    # the longest gap: from the end of step 1's last fusion (142,042,364)
    # to the next add (193,196,583), inside the batch span that slept
    name, secs = red["idle_gaps"][0]
    assert name == "batch"
    assert secs == pytest.approx(51.154219e-3, abs=1e-12)
    assert all(s >= tr.MIN_GAP_NS * 1e-9 for _, s in red["idle_gaps"])


def test_op_names_and_codes():
    t = ("%all-gather-start.3 = (bf16[8]{0}, bf16[32]{0}) "
         "all-gather-start(bf16[8]{0} %p), replica_groups={{0,1,2,3}}")
    assert tr.op_name(t) == "all-gather-start.3"
    assert tr.op_code(t) == "all-gather-start"
    assert tr.is_collective(t)
    f = "%fusion.3 = bf16[1024]{0} fusion(f32[1024]{0} %x), kind=kLoop"
    assert tr.op_code(f) == "fusion" and not tr.is_collective(f)
    assert tr.is_collective("%all-to-all.1 = f32[4,8]{1,0} all-to-all(...)")


def test_collective_and_exposed_time():
    coll = "%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %a)"
    comp = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop"
    ev = {"devices": {
        # device 0: compute 0-100, collective 50-150 (exposed 100-150),
        # compute 200-300, collective 250-260 (hidden)
        "/device:TPU:0": [(comp, 0, 100), (coll, 50, 100), (comp, 200, 100),
                          (coll, 250, 10)],
        # device 1: collective 0-40 alone (exposed 40), compute 60-300
        "/device:TPU:1": [(coll, 0, 40), (comp, 60, 240)]},
        "host": [("dispatch", 140, 30), ("wait", 100, 120)]}
    red = tr.reduce_events(ev, (0, 300))
    assert red["per_device"]["/device:TPU:0"]["collective_s"] == \
        pytest.approx(110e-9)
    assert red["per_device"]["/device:TPU:0"]["exposed_collective_s"] == \
        pytest.approx(50e-9)
    assert red["per_device"]["/device:TPU:0"]["busy_s"] == \
        pytest.approx(250e-9)
    assert red["per_device"]["/device:TPU:1"]["exposed_collective_s"] == \
        pytest.approx(40e-9)
    assert red["busy_s"] == pytest.approx((250e-9 + 280e-9) / 2)
    assert red["collective_s"] == pytest.approx((110e-9 + 40e-9) / 2)
    assert red["exposed_collective_s"] == pytest.approx(45e-9)
    # gaps of device 0 are under 1 us, so none is listed ...
    assert red["idle_gaps"] == []
    # ... and at microsecond scale the 150-200 gap falls to "wait"
    ev_us = {"devices": {k: [(t, s * 1e3, d * 1e3) for t, s, d in v]
                         for k, v in ev["devices"].items()},
             "host": [(n, s * 1e3, d * 1e3) for n, s, d in ev["host"]]}
    red = tr.reduce_events(ev_us, (0, 300e3))
    assert red["idle_gaps"] == [["wait", pytest.approx(50e-6)]]


def test_interval_helpers():
    assert tr.union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_self_time_of_nested_loop():
    loop = "%while.7 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)"
    body = "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop"
    ev = {"devices": {"/device:TPU:0": [(loop, 0, 100), (body, 10, 30),
                                         (body, 50, 20), (body, 120, 10)]},
          "host": []}
    red = tr.reduce_events(ev, (0, 130))
    assert red["op_s"]["while.7"] == pytest.approx(50e-9)
    assert red["op_s"]["fusion.2"] == pytest.approx(60e-9)
    assert red["loop_s"] == {"while.7": pytest.approx(100e-9)}
    assert sum(red["op_s"].values()) == pytest.approx(red["busy_s"])
