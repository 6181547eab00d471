"""The whole run after the look for a chip, at a tiny size on the CPU:
a sound program agrees with the reference to rounding and comes out
correct, and a program broken under the timed path comes out not
correct -- once for each fault a training cell can have: a step that
returns its state unchanged, half of the batch left out with the mean
taken over the rest, and (on four devices) the exchange between chips
left out; and the control, the reference computed in float8, in the
program's place, comes out not correct too.

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
SSM = {"family": "ssm", "num_layers": 2, "d_model": 64, "vocab_size": 512,
       "ssm_state": 16, "ssm_expand": 2, "ssm_head_dim": 16, "ssm_chunk": 8,
       "conv_width": 4, "tie_embeddings": True, "norm_eps": 1e-5,
       "dtype": "float32", "param_dtype": "float32"}
HYBRID = {**SSM, "family": "hybrid", "num_layers": 4, "num_heads": 4,
          "num_kv_heads": 4, "head_dim": 16, "d_ff": 128, "attn_every": 2,
          "tie_embeddings": False, "rope_theta": 10000.0}


def tiny_cell(model: dict, chips: int):
    fam = model["family"]
    wl = json.loads((BENCH / "workloads" / "mamba2-370m-l2.topk-qsgd.1chip.json")
                    .read_text())
    wl.update(chips=chips, seq_len=32, seqs_per_chip=4, microbatches=2,
              check_steps=2)
    # the learning rate each architecture's train config states
    wl["optimizer"] = {**wl["optimizer"],
                       "peak_lr": {"ssm": 6e-4, "hybrid": 3e-4}[fam]}
    wl["sync"] = {**wl["sync"], "qsgd_bits": None, "min_sparse_size": 0}
    wl["limits"] = dict.fromkeys(
        ("loss_gap", "ef_gap", "gnorm_gap", "grad_gap", "grad_med",
         "change_gap", "change_med"),
        TINY_LIMIT)
    arch = {"ssm": "mamba2_370m", "hybrid": "zamba2_2p7b"}[fam]
    keep = ("num_layers", "d_model", "vocab_size", "ssm_state",
            "ssm_head_dim", "ssm_chunk", "num_heads", "num_kv_heads",
            "head_dim", "d_ff", "attn_every", "dtype", "param_dtype")
    cfgf = {"name": f"tiny-{fam}", "arch": arch, "model": copy.deepcopy(model),
            "overrides": {**{k: model[k] for k in keep if k in model},
                          "max_seq_len": 128}}
    return wl, cfgf


def run_tiny(model, chips, fault=None):
    import jax

    from bench.run import run_cell

    wl, cfgf = tiny_cell(model, chips)
    # a zero-second window runs one step
    return run_cell(f"tiny-{model['family']}", wl, cfgf, 2**31 + 11, 0.0,
                    False, jax.devices()[:chips], time.perf_counter(),
                    fault=fault)


@pytest.mark.parametrize("model,chips", [(SSM, 1), (HYBRID, 1), (SSM, 4)],
                         ids=["ssm-1", "hybrid-1", "ssm-4"])
def test_sound_run_is_correct(model, chips):
    res = run_tiny(model, chips)
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
    res = run_tiny(SSM, 1, fault=state_unchanged)
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)
    assert res["checks"]["change_med"]["value"] == pytest.approx(1.0)


def test_fault_half_batch():
    res = run_tiny(SSM, 1, fault=half_batch)
    assert not res["correct"]
    assert res["checks"]["loss_gap"]["value"] > TINY_LIMIT
    assert res["checks"]["ef_gap"]["value"] > TINY_LIMIT
    assert res["checks"]["grad_gap"]["value"] > TINY_LIMIT


@pytest.mark.parametrize("model", [SSM, HYBRID], ids=["ssm", "hybrid"])
def test_control_fp8_is_not_correct(model):
    """The control: the reference computed in float8 where the program
    computes in its own precision, put in the program's place."""
    import jax

    from bench import check

    wl, cfgf = tiny_cell(model, 1)
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
    res = run_tiny(SSM, 4)
    assert not res["correct"]
    assert res["checks"]["grad_gap"]["value"] > TINY_LIMIT
