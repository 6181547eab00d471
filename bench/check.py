"""The comparison that decides ``correct``.

Readings of the first ``check_steps`` training steps, taken from the
program by ``bench/run.py`` and from the plain reference here:

* ``losses``: the mean loss of each step;
* ``gnorms``: the global norm of each step's synced gradient before
  clipping (the program returns it as the step's ``grad_norm``);
* ``ef``: the global norm of the error-feedback residuals after each
  step (what top-k left behind, before QSGD and clipping: about the raw
  gradient's norm, since 4 of 512 entries are sent);
* ``g1``: per leaf, the norm of the first step's gradient as the optimizer
  gets it (synced, quantised and clipped); the program's is read back from
  AdamW's first moment after one step, m = (1 - beta1) g;
* ``change``: per leaf, the norm of the parameters' change over the
  ``check_steps`` steps, in the dtype the parameters are stored in.

The numbers (``gaps``), of which the cell's ``limits`` name the ones
compared, each against its limit:

* ``loss_gap``: the largest |loss - ref| / |ref| over the steps;
* ``ef_gap`` and ``gnorm_gap``: the same for ``ef`` and ``gnorms``;
* ``grad_gap``: over leaves, the largest gap between the two norms,
  |g - g_ref|, over the larger of the leaf's reference norm and the
  median leaf's; ``grad_med``: the median of those gaps;
* ``change_gap`` and ``change_med``: the same for the change, over the
  leaves whose reference gradient is not nought to rounding (at least a
  thousandth of the median leaf's).
"""
from __future__ import annotations

import math
import statistics

import numpy as np

from bench import reference, traffic

LIVE_FRACTION = 1e-3


def opt_of(wl: dict) -> reference.Opt:
    s, o = wl["sync"], wl["optimizer"]
    return reference.Opt(k=s["k_per_bucket"], bucket=s["bucket_size"],
                         qsgd_bits=s["qsgd_bits"],
                         qsgd_bucket=s["qsgd_bucket"], **o)


def norm(x) -> float:
    a = np.asarray(x, np.float64)
    return float(np.sqrt(np.sum(a * a)))


def reference_readings(cfgf: dict, wl: dict, seed: int, devices,
                       prec: str = "f32", fault: str | None = None) -> dict:
    """Readings of the reference over the cell's first steps, the model
    the configuration's ``"reference"`` module. ``fault``
    plants one in the reference put in the program's place:
    ``"half_batch"`` (each rank's mean over the first half of its rows)
    or ``"no_exchange"`` (each rank keeps its own gradient)."""
    mod = reference.arch_module(cfgf["reference"])
    a, o = mod.arch(cfgf["model"]), opt_of(wl)
    chips = wl["chips"]
    tr = reference.Trainer(seed, mod, a, o, ranks=chips, prec=prec)
    p0 = dict(tr.params)
    losses, gnorms, ef, g1 = [], [], [], None
    for step in range(wl["check_steps"]):
        rows = traffic.rows(seed, step, wl["seqs_per_chip"] * chips,
                            wl["seq_len"], cfgf["model"]["vocab_size"])
        by_rank = np.split(rows, chips)
        if fault == "half_batch":
            by_rank = [r[: len(r) // 2] for r in by_rank]
        loss, synced, gnorm = tr.train_step(by_rank, devices,
                                            exchange=fault != "no_exchange")
        losses.append(loss)
        gnorms.append(gnorm)
        ef.append(tr.ef_norm)
        if step == 0:
            g1 = {k: norm(v) for k, v in synced.items()}
    change = {k: norm(np.asarray(tr.params[k], np.float64)
                      - np.asarray(p0[k], np.float64)) for k in p0}
    return {"losses": losses, "gnorms": gnorms, "ef": ef, "g1": g1,
            "change": change}


def leaf_gaps(got: dict, ref: dict, keys) -> dict:
    """Per leaf, |got - ref| over the larger of the leaf's reference norm
    and the median leaf's."""
    med = statistics.median(ref[k] for k in keys)
    out = {}
    for k in keys:
        den = max(ref[k], med)
        out[k] = abs(got[k] - ref[k]) / den if den > 0 else (
            0.0 if got[k] == 0 else math.inf)
    return out


def _worst(got: dict, ref: dict, keys) -> tuple[float, str]:
    g = leaf_gaps(got, ref, keys)
    at = max(g, key=g.get)
    return g[at], at


def gaps(prog: dict, ref: dict) -> dict:
    """The three compared numbers, with the leaf that set each."""
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(prog["losses"], ref["losses"]))
    if any(not math.isfinite(x) for x in prog["losses"]):
        loss_gap = math.inf
    grad_gap, grad_at = _worst(prog["g1"], ref["g1"], ref["g1"])
    med = statistics.median(ref["g1"].values())
    live = [k for k, v in ref["g1"].items() if v >= LIVE_FRACTION * med]
    change_gap, change_at = _worst(prog["change"], ref["change"], live)
    grad_med = statistics.median(leaf_gaps(prog["g1"], ref["g1"],
                                           ref["g1"]).values())
    change_med = statistics.median(leaf_gaps(prog["change"], ref["change"],
                                             live).values())
    rel = lambda a, b: max(abs(p - r) / r for p, r in zip(a, b))
    gnorm_gap = rel(prog["gnorms"], ref["gnorms"])
    ef_gap = rel(prog["ef"], ref["ef"])
    return {"loss_gap": loss_gap, "grad_gap": grad_gap, "grad_at": grad_at,
            "change_gap": change_gap, "change_at": change_at,
            "grad_med": grad_med, "change_med": change_med,
            "gnorm_gap": gnorm_gap, "ef_gap": ef_gap}


def compare(prog: dict, ref: dict, limits: dict) -> dict:
    """Each number the cell's ``limits`` name, beside its limit."""
    g = gaps(prog, ref)
    return {k: {"value": g[k], "limit": v} for k, v in limits.items()}
