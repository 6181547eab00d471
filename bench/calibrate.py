"""Readings that set a cell's limits: the program on many seeds, the
control and the planted faults on a few, all in one process.

    python bench/calibrate.py --workload <cell> --seeds 11,12,... \\
        --control-seeds 11,12,13 --fault-seeds 11,12,13 --out <file.jsonl>

Per seed it builds nothing new: one ``Trainer`` (one compiled step) is
re-initialised from each seed and driven through the cell's first steps,
as ``bench/run.py`` drives it in set-up; then the reference replays them.
Each line written is one reading:

* ``program``: the program against the float32 reference;
* ``control``: the reference computed in float8 (contraction operands
  and the residual stream in e4m3, their gradients in e5m2), put in the
  program's place;
* ``fault:half_batch``: the reference that averages over half of each
  rank's rows, in the program's place;
* ``fault:no_exchange`` (cells on several chips): the reference whose
  ranks keep their own gradients.

A step that returns its state unchanged reads 1 on ``change_gap`` and
about 1 on ``change_med`` by construction and needs no run. Each line keeps both sides' readings, so

    python bench/calibrate.py --workload <cell> --judge <file.jsonl>

passes every line through ``bench/check.compare`` with the cell's limits
as they stand and prints whether it comes out correct.

``--probe-seeds`` adds the look behind the gradient numbers: on each of
those seeds, the program's raw gradient of the first step (its own
microbatched forward and backward, before any sync) beside the
reference's, per leaf, and the reference's top-k and QSGD applied to
both, so that a gap can be laid to the raw gradient, the top-k picks or
the QSGD draws. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import check, reference, run, traffic  # noqa: E402


def reseed(trainer, model, seed: int) -> None:
    import jax

    from repro.runtime.driver import DriverLog
    from repro.train.train_step import init_state

    trainer.tcfg = dataclasses.replace(trainer.tcfg, seed=seed)
    trainer.data_cfg = dataclasses.replace(trainer.data_cfg, seed=seed)
    trainer._root_key = jax.random.PRNGKey(seed)
    trainer.log = DriverLog()
    trainer.state = None
    trainer.state, _ = init_state(model, trainer.tcfg, trainer.mesh)


def probe(trainer, model, wl, cfgf, seed, devices) -> dict:
    """Per leaf, the first step's gradient of program and reference
    before sync, and after the reference's top-k and QSGD."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.train.train_step import _accumulated_grads

    mod = reference.arch_module(cfgf["reference"])
    a, o = mod.arch(cfgf["model"]), check.opt_of(wl)
    rows = traffic.rows(seed, 0, wl["seqs_per_chip"] * wl["chips"],
                        wl["seq_len"], cfgf["model"]["vocab_size"])
    reseed(trainer, model, seed)
    batch = {"tokens": jnp.asarray(rows), "labels": jnp.asarray(rows)}
    grad = jax.jit(lambda p, b: _accumulated_grads(
        model, p, b, wl["microbatches"])[1])
    gp = run.flat_host(grad(trainer.state.params, batch))
    trainer.state = None
    tr = reference.Trainer(seed, mod, a, o, ranks=1, prec="f32")
    _, acc = reference.rank_grads(tr.unflat(tr.params), rows, mod.seq_loss,
                                  a, "f32", devices[0])
    gr = {k: np.asarray(v) / len(rows) for k, v in reference.flat(
        acc).items()}
    del acc
    key = jax.random.fold_in(jax.random.PRNGKey(seed ^ 0x5EED), 0)
    other = jax.random.fold_in(jax.random.PRNGKey(seed ^ 0xD1CE), 0)
    out = {}
    for i, name in enumerate(tr.params):
        p, r = np.asarray(gp[name], np.float32), gr[name]
        ax = tr.axis(name, p.ndim)
        res0 = jnp.zeros(reference.to_rows(jnp.asarray(r), ax, o.bucket).shape)
        kp, _ = reference.topk_ef(jnp.asarray(p), res0, 1.0, ax, o.k, o.bucket)
        kr, _ = reference.topk_ef(jnp.asarray(r), res0, 1.0, ax, o.k, o.bucket)
        q = lambda x, k: reference.qsgd(x, jax.random.fold_in(k, i),
                                        o.qsgd_bits, o.qsgd_bucket)
        picked_p, picked_r = np.asarray(kp) != 0, np.asarray(kr) != 0
        out[name] = {
            "raw": [check.norm(p), check.norm(r)],
            "raw_diff": check.norm(p - r) / max(check.norm(r), 1e-30),
            "topk": [check.norm(kp), check.norm(kr)],
            "same_picks": float(np.sum(picked_p & picked_r)
                                / max(np.sum(picked_r), 1)),
            "qsgd": [check.norm(q(kp, key)), check.norm(q(kr, key))],
            "qsgd_other_draw": check.norm(q(kr, other))}
    return out


def judge(path: Path, limits: dict) -> None:
    """Each recorded line through the harness's comparison."""
    for line in path.read_text().splitlines():
        d = json.loads(line)
        if "got" not in d:
            continue
        c = check.compare(d["got"], d["ref"], limits)
        ok = all(v["value"] <= v["limit"] for v in c.values())
        print(json.dumps({"kind": d["kind"], "seed": d["seed"],
                          "correct": ok, **{k: v["value"]
                                            for k, v in c.items()}}))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--probe-seeds", default="")
    ap.add_argument("--out")
    ap.add_argument("--judge")
    args = ap.parse_args()
    ints = lambda s: [int(x) for x in s.split(",") if x]
    seeds, cseeds, fseeds = (ints(args.seeds), ints(args.control_seeds),
                             ints(args.fault_seeds))
    wl, cfgf = run.load_cell(args.workload)
    if args.judge:
        judge(Path(args.judge), wl["limits"])
        return 0
    pseeds = ints(args.probe_seeds)

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.train.trainer import Trainer

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != wl["chips"]:
        print(f"calibrate: needs {wl['chips']} TPU chip(s)", file=sys.stderr)
        return 2
    enable_compile_cache()
    chips = wl["chips"]
    first = (seeds + cseeds + fseeds + pseeds)[0]
    model, tcfg, data = run.build_program(wl, cfgf, first, chips)
    mesh = make_host_mesh(data=chips)
    run.check_plan(model, tcfg, mesh)
    trainer = Trainer(model, tcfg, mesh, data)
    faults = ["half_batch"] + (["no_exchange"] if chips > 1 else [])
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)

    def emit(kind, seed, got, ref, t0):
        g = check.gaps(got, ref)
        c = check.compare(got, ref, wl["limits"])
        line = {"cell": args.workload, "kind": kind, "seed": seed,
                "correct": all(v["value"] <= v["limit"] for v in c.values()),
                **g, "seconds": time.perf_counter() - t0}
        with out.open("a") as f:
            f.write(json.dumps({**line, "got": got, "ref": ref}) + "\n")
        print(json.dumps(line), flush=True)

    for seed in sorted(set(seeds) | set(cseeds) | set(fseeds)):
        t0 = time.perf_counter()
        reseed(trainer, model, seed)
        prog = run.readings(run.first_steps(trainer, wl["check_steps"]))
        trainer.state = None
        t1 = time.perf_counter()
        ref = check.reference_readings(cfgf, wl, seed, devices)
        print(f"seed {seed}: program {t1 - t0:.1f} s, reference "
              f"{time.perf_counter() - t1:.1f} s", flush=True)
        if seed in seeds:
            emit("program", seed, prog, ref, t0)
        if seed in cseeds:
            t0 = time.perf_counter()
            emit("control", seed, check.reference_readings(
                cfgf, wl, seed, devices, prec="fp8"), ref, t0)
        if seed in fseeds:
            for fault in faults:
                t0 = time.perf_counter()
                emit(f"fault:{fault}", seed, check.reference_readings(
                    cfgf, wl, seed, devices, fault=fault), ref, t0)
    for seed in pseeds:
        t0 = time.perf_counter()
        look = probe(trainer, model, wl, cfgf, seed, devices)
        with out.open("a") as f:
            f.write(json.dumps({"cell": args.workload, "kind": "probe",
                                "seed": seed, "leaves": look}) + "\n")
        print(f"probe seed {seed}: {time.perf_counter() - t0:.1f} s")
        for k, v in look.items():
            print(f"  {k}: {json.dumps(v)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
