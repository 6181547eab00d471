"""The benchmark's data files: every configuration, workload and metric
reader that BENCHMARK.json names loads, and keeps the contract's
characters and keys; the peaks lookup refuses an unknown chip; the
harness refuses to run without a TPU."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}


def test_names_and_units():
    names = [c["name"] for c in SPEC["configs"]]
    names += [w["name"] for w in SPEC["workloads"]]
    names += [w["traffic"] for w in SPEC["workloads"]]
    names += [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += [k for c in SPEC["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for w in SPEC["workloads"]:
        assert len(w["why"]) <= 200 and "\n" not in w["why"]


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files(cfg):
    f = json.loads((ROOT / cfg["file"]).read_text())
    assert f["name"] == cfg["name"] and f["source"] == cfg["source"]
    assert f["reduced"] == cfg["reduced"]
    for key in ("arch", "model", "published", "assumed", "deployment"):
        assert key in f, key


@pytest.mark.parametrize("wl", SPEC["workloads"], ids=lambda w: w["name"])
def test_workload_files(wl):
    from bench.run import load_cell

    w, cfg = load_cell(wl["name"])
    assert w["config"] == wl["config"] == cfg["name"]
    assert w["traffic"] == wl["traffic"] and w["chips"] == wl["chips"]
    assert w["why"] == wl["why"]
    assert w["limits"] and set(w["limits"]) <= {
        "loss_gap", "ef_gap", "gnorm_gap", "grad_gap", "grad_med",
        "change_gap", "change_med"}
    assert w["seq_len"] % cfg["model"]["ssm_chunk"] == 0
    assert w["seqs_per_chip"] % w["microbatches"] == 0


@pytest.mark.parametrize("m", SPEC["per_layer"], ids=lambda m: m["name"])
def test_metric_readers(m):
    from bench.run import metric_reader

    read = metric_reader(m["name"])
    facts = {"chips": 4, "steps": 2, "tokens_per_step": 10,
             "flops_per_token": 1.0, "peak_flops_per_s": 1.0}
    assert read({"n_devices": 0}, facts) is None
    red = {"n_devices": 4, "window_s": 100.0, "busy_s": 90.0,
           "collective_s": 0.5, "exposed_collective_s": 0.25}
    v = read(red, facts)
    assert v is not None and v >= 0
    assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}


def test_unknown_device_kind_raises():
    from bench.peaks import peak

    assert peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peak("TPU v9 imaginary")


def test_run_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = SPEC["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
