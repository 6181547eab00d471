"""The benchmark's data files: every configuration, workload and metric
reader that BENCHMARK.json names loads, and keeps the contract's
characters and keys; every configuration file's reference module loads;
a new architecture joins with new files alone; the peaks lookup refuses
an unknown chip; the harness refuses to run without a TPU."""
import json
import os
import re
import subprocess
import sys
import textwrap
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
    for key in ("arch", "reference", "model", "published", "assumed",
                "deployment"):
        assert key in f, key


@pytest.mark.parametrize("path", sorted((BENCH / "configs").glob("*.json")),
                         ids=lambda p: p.stem)
def test_config_reference_module(path):
    from bench.reference import arch_module

    cfg = json.loads(path.read_text())
    mod = arch_module(cfg["reference"])
    a = mod.arch(cfg["model"])
    assert a.vocab_size == cfg["model"]["vocab_size"]
    terms = mod.flops_per_token(cfg["model"], 4096)
    assert terms["total"] == sum(v for k, v in terms.items() if k != "total")
    assert terms["total"] > 0


def test_missing_reference_module_names_its_path(tmp_path, monkeypatch):
    from bench import reference

    monkeypatch.setattr(reference, "ARCHS", tmp_path)
    with pytest.raises(FileNotFoundError, match=re.escape(
            str(tmp_path / "no_such_model.py"))):
        reference.arch_module("no_such_model")


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
    assert w["seq_len"] % cfg["model"].get("ssm_chunk", 1) == 0
    assert w["seqs_per_chip"] % w["microbatches"] == 0


@pytest.mark.parametrize("m", SPEC["per_layer"], ids=lambda m: m["name"])
def test_metric_readers(m):
    from bench.run import metric_reader

    read = metric_reader(m["name"])
    facts = {"chips": 4, "steps": 2, "tokens_per_step": 10,
             "flops_per_token": 1.0, "peak_flops_per_s": 1.0}
    assert read({"n_devices": 0}, facts) is None
    scopes = {"model": 0.4, "sync": 0.01, "sync/compress": 0.02,
              "sync/qsgd": 0.03, "update": 0.04}
    red = {"n_devices": 4, "window_s": 100.0, "busy_s": 90.0,
           "collective_s": 0.5, "exposed_collective_s": 0.25,
           "scope_s": scopes, "scope_incl_s": {**scopes, "sync": 0.06},
           "host_span_s": {"train/run": 0.5, "train/wait": 0.4}}
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


TOY = textwrap.dedent('''
    """A toy architecture: embedding, one residual SiLU MLP, tied head."""
    import math
    from dataclasses import dataclass

    import jax
    import jax.numpy as jnp

    from bench.reference import act, ein, normal, rmsnorm


    @dataclass(frozen=True)
    class Arch:
        d_model: int
        d_ff: int
        vocab_size: int
        param_dtype: str = "float32"


    def arch(model):
        return Arch(model["d_model"], model["d_ff"], model["vocab_size"])


    def init_params(seed, a):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        d, f, v = a.d_model, a.d_ff, a.vocab_size
        return {"embed": normal(ks[0], (v, d), 0.02, a.param_dtype),
                "norm": {"scale": jnp.ones((d,), a.param_dtype)},
                "in_proj": normal(ks[1], (d, f), d ** -0.5, a.param_dtype),
                "out_proj": normal(ks[2], (f, d), f ** -0.5, a.param_dtype)}


    def seq_loss(params, tokens, a, prec):
        x = act(prec, params["embed"][tokens])
        u = rmsnorm(x, params["norm"]["scale"], 1e-5)
        h = jax.nn.silu(ein(prec, "sd,df->sf", u, params["in_proj"]))
        x = act(prec, x + ein(prec, "sf,fd->sd", h, params["out_proj"]))
        logits = ein(prec, "sd,vd->sv", x, params["embed"])
        logp = jax.nn.log_softmax(logits[:-1], axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], -1))


    def flops_per_token(model, seq_len):
        """matmul: 6 x (2 d f + d V)."""
        d, f, v = model["d_model"], model["d_ff"], model["vocab_size"]
        terms = {"matmul": 6 * (2 * d * f + d * v)}
        terms["total"] = sum(terms.values())
        return terms
    ''')

TOY_READER = textwrap.dedent('''
    """mlp_ms: device time per step of the toy's MLP scope."""


    def read(red, facts):
        secs = red.get("scope_incl_s", {}).get("model/mlp")
        return None if secs is None else 1e3 * secs / facts["steps"]
    ''')


def test_new_architecture_joins_with_new_files(tmp_path, monkeypatch):
    """A module, a configuration and a reader file, written outside
    ``bench/``: the reference replays the cell's first steps on the toy,
    its FLOPs are counted, and the reader of its new scope reads it."""
    import jax
    import math

    from bench import check, reference, run, scope_reduce
    from bench.tests.test_bench_faults import tiny_cell

    wl, _ = tiny_cell("mamba2", 1)
    (tmp_path / "archs").mkdir()
    (tmp_path / "archs" / "toy.py").write_text(TOY)
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "mlp_ms.py").write_text(TOY_READER)
    cfg_path = tmp_path / "toy-mlp.json"
    cfg_path.write_text(json.dumps({
        "name": "toy-mlp", "reference": "toy",
        "model": {"d_model": 32, "d_ff": 64, "vocab_size": 256}}))
    monkeypatch.setattr(reference, "ARCHS", tmp_path / "archs")
    monkeypatch.setattr(run, "METRICS", tmp_path / "metrics")

    cfgf = json.loads(cfg_path.read_text())
    got = check.reference_readings(cfgf, wl, 2**31 + 3, jax.devices()[:1])
    assert set(got["g1"]) == {"['embed']", "['in_proj']", "['norm']['scale']",
                              "['out_proj']"}
    assert len(got["losses"]) == wl["check_steps"]
    # at the start the loss is about that of a uniform guess
    assert abs(got["losses"][0] - math.log(256)) < 0.1
    assert all(c > 0 for c in got["change"].values())
    again = check.reference_readings(cfgf, wl, 2**31 + 3, jax.devices()[:1])
    assert again == got
    control = check.reference_readings(cfgf, wl, 2**31 + 3,
                                       jax.devices()[:1], prec="fp8")
    assert check.gaps(control, got)["loss_gap"] > 0

    mod = reference.arch_module(cfgf["reference"])
    assert mod.flops_per_token(cfgf["model"], 32) == {
        "matmul": 6 * (2 * 32 * 64 + 32 * 256), "total": 73_728}

    fusion = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop"
    dot = "%dot.2 = f32[8]{0} dot(f32[8]{0} %a, f32[8]{0} %b)"
    red = scope_reduce.reduce_with_program(
        {"devices": {"/device:TPU:0": [(fusion, 0, 2e6), (dot, 2e6, 3e6)]},
         "async": {}, "host": [("step", 0, 1e7)]}, [],
        {"fusion.1": "model", "dot.2": "model/mlp"})
    facts = {"chips": 1, "steps": 1}
    assert run.metric_reader("mlp_ms")(red, facts) == pytest.approx(3.0)
    # the model's reader keeps its meaning: the whole model, MLP included
    monkeypatch.setattr(run, "METRICS", BENCH / "metrics")
    assert run.metric_reader("model_ms")(red, facts) == pytest.approx(5.0)
