"""The whole run after the look for a chip, at a tiny size on the CPU:
a sound program agrees with the reference to rounding and comes out
correct, and a program broken under the timed path comes out not
correct -- once for each fault a training cell can have: a step that
returns its state unchanged, half of the batch left out with the mean
taken over the rest, and (on four devices) the exchange between chips
left out; and the control, the reference computed in float8, in the
program's place, comes out not correct too.

Each model's reference module (``bench/archs/<name>.py``) brings its tiny
cell, ``TINY``: the sound run and the control run on every one of them.
The tiny cells run in float32 without QSGD (whose stochastic rounding
draws differ between program and reference), so the limits here are
tight: a sound run reads about 1e-7."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import copy  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
from pathlib import Path  # noqa: E402

import pytest  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
TINY_LIMIT = 1e-4
ARCHS = sorted(p.stem for p in (BENCH / "archs").glob("*.py"))


def family(ref: str) -> str:
    from bench.reference import arch_module

    return arch_module(ref).TINY["model"]["family"]


def tiny_cell(ref: str, chips: int):
    """The tiny cell of the reference module ``ref`` on ``chips`` devices:
    the one-chip cell's sync and optimizer at the learning rate the
    program's train config states, float32, no QSGD, tight limits."""
    from bench.reference import arch_module
    from repro import configs

    tiny = arch_module(ref).TINY
    model = copy.deepcopy(tiny["model"])
    wl = json.loads((BENCH / "workloads" / "mamba2-370m-l2.topk-qsgd.1chip.json")
                    .read_text())
    wl.update(chips=chips, seq_len=32, seqs_per_chip=4, microbatches=2,
              check_steps=2)
    lr = configs.get_train_config(tiny["arch"]).schedule.peak_lr
    wl["optimizer"] = {**wl["optimizer"], "peak_lr": lr}
    wl["sync"] = {**wl["sync"], "qsgd_bits": None, "min_sparse_size": 0}
    wl["limits"] = dict.fromkeys(
        ("loss_gap", "ef_gap", "gnorm_gap", "grad_gap", "grad_med",
         "change_gap", "change_med"),
        TINY_LIMIT)
    cfgf = {"name": f"tiny-{ref}", "arch": tiny["arch"], "reference": ref,
            "model": model, "overrides": {**model, "max_seq_len": 128}}
    return wl, cfgf


def run_tiny(ref, chips, fault=None):
    import jax

    from bench.run import run_cell

    wl, cfgf = tiny_cell(ref, chips)
    # a zero-second window runs one step
    return run_cell(cfgf["name"], wl, cfgf, 2**31 + 11, 0.0,
                    False, jax.devices()[:chips], time.perf_counter(),
                    fault=fault)


@pytest.mark.parametrize("ref,chips", [(r, c) for c in (1, 4) for r in ARCHS],
                         ids=lambda v: family(v) if isinstance(v, str)
                         else str(v))
def test_sound_run_is_correct(ref, chips):
    res = run_tiny(ref, chips)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= 1 and res["failed"] == 0
    for c in res["checks"].values():
        assert c["value"] < TINY_LIMIT / 10


def state_unchanged(step_fn):
    import jax.numpy as jnp
    import jax

    def broken(state, batch, key):
        new, metrics = step_fn(jax.tree.map(jnp.copy, state), batch, key)
        return state._replace(step=new.step), metrics
    return broken


def half_batch(step_fn):
    def broken(state, batch, key):
        return step_fn(state, {k: v[: v.shape[0] // 2]
                               for k, v in batch.items()}, key)
    return broken


def test_fault_state_unchanged():
    res = run_tiny("mamba2", 1, fault=state_unchanged)
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)
    assert res["checks"]["change_med"]["value"] == pytest.approx(1.0)


def test_fault_half_batch():
    res = run_tiny("mamba2", 1, fault=half_batch)
    assert not res["correct"]
    assert res["checks"]["loss_gap"]["value"] > TINY_LIMIT
    assert res["checks"]["ef_gap"]["value"] > TINY_LIMIT
    assert res["checks"]["grad_gap"]["value"] > TINY_LIMIT


@pytest.mark.parametrize("ref", ARCHS, ids=family)
def test_control_fp8_is_not_correct(ref):
    """The control: the reference computed in float8 where the program
    computes in its own precision, put in the program's place."""
    import jax

    from bench import check

    wl, cfgf = tiny_cell(ref, 1)
    seed, devices = 2**31 + 11, jax.devices()[:1]
    ref = check.reference_readings(cfgf, wl, seed, devices)
    control = check.reference_readings(cfgf, wl, seed, devices, prec="fp8")
    checks = check.compare(control, ref, wl["limits"])
    assert not all(c["value"] <= c["limit"] for c in checks.values())
    assert checks["loss_gap"]["value"] > TINY_LIMIT


def test_fault_exchange_left_out(monkeypatch):
    import jax.numpy as jnp

    from repro.comm.collectives import CollectiveContext

    # every rank keeps its own payload: the all-to-all sends nothing and
    # the all-gather repeats the rank's own chunk
    monkeypatch.setattr(CollectiveContext, "all_to_all",
                        lambda self, x, *, axis: x)
    monkeypatch.setattr(
        CollectiveContext, "all_gather",
        lambda self, x, *, axis: jnp.concatenate([x] * self.p, axis=axis))
    res = run_tiny("mamba2", 4)
    assert not res["correct"]
    assert res["checks"]["grad_gap"]["value"] > TINY_LIMIT
