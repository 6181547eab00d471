"""Chip benchmark of sparse-sync training: one cell, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``bench/workloads/<cell>.json``; it names its configuration,
``bench/configs/<config>.json``, which names its model's plain reference,
``bench/archs/<reference>.py`` (a missing one fails at the start). One
process owns all the chips, and the
run refuses (exit 2, no result) unless JAX sees TPUs, as many as the cell
asks for. It keeps JAX's compile cache where
``repro.launch.compile_cache.enable_compile_cache`` puts it (inside the
checkout, or ``JAX_COMPILATION_CACHE_DIR``).

Set-up (``setup_s``, from process start): build the model and the train
step, make the weights on the device from the seed, and drive the cell's
first ``check_steps`` steps through ``Trainer.run`` -- the same object and
call the window uses; the first compiles. Window: ``Trainer.run(step + 1)`` until
``--seconds`` have passed; ``tokens_per_s`` is the tokens of those whole
steps over first dispatch to last ``block_until_ready``. After the
window: peak device memory, then the program's state is freed and the
plain reference (``bench/reference.py`` with the model's module) replays
those first steps from the same seed; ``correct`` holds when each
compared number is within the cell's limit (``bench/check.py``).

``--trace 1`` runs the program with its own tracing on (the ``train/*``
spans of ``Trainer.run``), takes the step's ``{instruction: scope}`` map
from the executable the window runs (:func:`step_scopes`), traces the
window with ``jax.profiler`` under ``bench/traces/``, reduces it with
``bench/scope_reduce.py`` and reports the cell's per-layer metrics, each
computed by its reader in ``bench/metrics/<metric>.py``.

The last line of stdout is one JSON object; the compared numbers with
their limits are also the last lines of stderr.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
METRICS = BENCH / "metrics"
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str) -> tuple[dict, dict]:
    """(workload, configuration) of a cell, found by name; raises where the
    configuration's reference module is missing."""
    from bench import reference

    wl = load_json(BENCH / "workloads" / f"{name}.json")
    cfg = load_json(BENCH / "configs" / f"{wl['config']}.json")
    reference.arch_module(cfg["reference"])
    return wl, cfg


def per_layer_metrics(cell: str) -> list[dict]:
    """The ``per_layer`` entries of BENCHMARK.json that this cell reports."""
    spec = load_json(ROOT / "BENCHMARK.json")
    e2e = {m["name"] for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])}
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", [cell]) and m["moves"] in e2e]


def metric_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileLog:
    """Counts backend compiles (persistent-cache hits included) through a
    ``jax.monitoring`` listener."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.names.append(str(kw.get("fun_name", "?")))

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


# ---------------------------------------------------------------- program

def build_program(wl: dict, cfgf: dict, seed: int, chips: int):
    """(model, train config, data config) as the cell's files state them;
    raises when the program's configuration differs from the file."""
    import dataclasses

    import jax.numpy as jnp
    from repro import configs
    from repro.configs._common import default_sync
    from repro.data.pipeline import DataConfig
    from repro.models.model import build_model

    over = {k: jnp.dtype(v) if k in ("dtype", "param_dtype") else v
            for k, v in cfgf.get("overrides", {}).items()}
    cfg = configs.get_config(cfgf["arch"], **over)
    for key, want in cfgf["model"].items():
        have = getattr(cfg, key)
        if key in ("dtype", "param_dtype"):
            have = jnp.dtype(have).name
        if have != want:
            raise ValueError(f"{cfgf['name']}: program has {key}={have!r}, "
                             f"the configuration file states {want!r}")
    s = wl["sync"]
    tcfg = configs.get_train_config(cfgf["arch"],
                                    microbatches=wl["microbatches"])
    sync = dataclasses.replace(
        default_sync(s["mode"], k=s["k_per_bucket"], qsgd_bits=s["qsgd_bits"]),
        bucket_size=s["bucket_size"], qsgd_bucket=s["qsgd_bucket"],
        **{k: s[k] for k in ("min_sparse_size",) if k in s})
    tcfg = dataclasses.replace(tcfg, sync=sync, zero1=s["zero1"], seed=seed)
    o = wl["optimizer"]
    have = {"peak_lr": tcfg.schedule.peak_lr,
            "warmup": tcfg.schedule.warmup_steps,
            "total_steps": tcfg.schedule.total_steps,
            "final_frac": tcfg.schedule.final_frac,
            "beta1": tcfg.optimizer.beta1, "beta2": tcfg.optimizer.beta2,
            "eps": tcfg.optimizer.eps,
            "weight_decay": tcfg.optimizer.weight_decay,
            "grad_clip": tcfg.optimizer.grad_clip}
    if tcfg.schedule.kind != "cosine" or tcfg.optimizer.kind != "adamw" or any(
            not math.isclose(have[k], o[k]) for k in have):
        raise ValueError(f"{cfgf['name']}: program optimizer {have} differs "
                         f"from the workload's {o}")
    data = DataConfig(global_batch=wl["seqs_per_chip"] * chips,
                      seq_len=wl["seq_len"], vocab_size=cfg.vocab_size,
                      seed=seed)
    return build_model(cfg), tcfg, data


def check_plan(model, tcfg, mesh) -> None:
    """The reference compresses every leaf: refuse a plan in which some
    bucket rides the wire uncompressed or without DSAR's QSGD phase."""
    from repro.train.train_step import state_shapes

    _, _, plan = state_shapes(model, tcfg, mesh, return_plan=True)
    want = "dsar_split_allgather" if tcfg.sync.qsgd_bits else None
    for b in plan.buckets:
        if not b.has_residual or (want and b.algorithm != want):
            raise ValueError(f"bucket {b.name}: algorithm {b.algorithm}, "
                             f"error feedback {b.has_residual}; the "
                             f"reference compresses every bucket")


def device_norms(tree):
    """L2 norm of every leaf, computed where the leaves live."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
        t))(tree)


def change_norms(p0: dict, p1: dict) -> dict:
    import numpy as np

    return {k: float(np.sqrt(np.sum(np.square(
        np.asarray(p1[k], np.float64) - np.asarray(p0[k], np.float64)))))
        for k in p0}


def flat_host(tree) -> dict:
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    got = jax.device_get([v for _, v in leaves])
    return {jax.tree_util.keystr(k): v for (k, _), v in zip(leaves, got)}


class StepNorms:
    """Wraps the step function and keeps the ``grad_norm`` each call
    returns (the synced gradient's global norm before clipping)."""

    def __init__(self, trainer):
        self.trainer, self.inner, self.norms = trainer, trainer.step_fn, []

    def __enter__(self):
        def step(*a, **kw):
            new, metrics = self.inner(*a, **kw)
            self.norms.append(metrics["grad_norm"])
            return new, metrics
        self.trainer.step_fn = step
        return self

    def __exit__(self, *exc):
        self.trainer.step_fn = self.inner


def first_steps(trainer, n_check: int) -> dict:
    """Drive a freshly initialised trainer through its first ``n_check``
    steps with ``Trainer.run`` and keep what the check reads: the
    parameters before and after, the first step's gradient norms from
    AdamW's first moment, the losses, the pre-clip global norms and the
    error-feedback residuals' global norm after each step."""
    p0 = flat_host(trainer.state.params)
    ef = []
    with StepNorms(trainer) as sn:
        for step in range(1, n_check + 1):
            trainer.run(step)
            ef.append(math.sqrt(sum(float(v) ** 2 for v in flat_host(
                device_norms(trainer.state.residuals)).values())))
            if step == 1:
                b1 = trainer.tcfg.optimizer.beta1
                g1 = {k: float(v) / (1 - b1) for k, v in flat_host(
                    device_norms(trainer.state.opt["mu"])).items()}
    return {"p0": p0, "p_end": flat_host(trainer.state.params), "g1": g1,
            "losses": list(trainer.log.losses[:n_check]),
            "gnorms": [float(x) for x in sn.norms], "ef": ef}


def step_text(step_fn, *args) -> str:
    """The compiled step's text, from a compile with the persistent
    compilation cache off. That cache keys a module without its
    ``op_name`` metadata, so a hit returns the text of whichever program
    wrote the entry: another commit's scopes, or none. Called before the
    step first runs, the compile also serves the step's calls (JAX's
    in-memory cache holds it), so nothing compiles twice."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return step_fn.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def step_scopes(trainer) -> dict[str, str]:
    """``{instruction: scope}`` of the step executable that ``trainer``'s
    calls run, compiled here for the arguments of its first call as
    ``Trainer.run`` makes them."""
    import jax

    from repro.data.pipeline import synthetic_batch
    from repro.obs.scopes import op_scopes

    batch = jax.tree.map(jax.numpy.asarray,
                         synthetic_batch(trainer.data_cfg, 0))
    key = jax.random.fold_in(trainer._root_key, 0)
    with trainer.mesh:
        return op_scopes(step_text(trainer.step_fn, trainer.state, batch,
                                   key))


def window(trainer, seconds: float) -> tuple[int, float, float]:
    """The measured window: ``Trainer.run(step + 1)`` under a ``step``
    span until ``seconds`` have passed; (steps, start, end) on the host
    clock, the end after the last step's ``block_until_ready``."""
    import jax

    t0 = time.perf_counter()
    steps = 0
    while True:
        with jax.profiler.TraceAnnotation("step"):
            trainer.run(int(trainer.state.step) + 1)
        steps += 1
        if time.perf_counter() - t0 >= seconds:
            return steps, t0, time.perf_counter()


def readings(first: dict) -> dict:
    return {"losses": first["losses"], "gnorms": first["gnorms"],
            "ef": first["ef"], "g1": first["g1"],
            "change": change_norms(first["p0"], first["p_end"])}


@contextlib.contextmanager
def annotated(trainer_mod, trainer, fault=None):
    """Host spans around the program's own calls, written into the
    profiler's trace: ``batch`` (making the step's rows) and ``dispatch``
    (the jitted step call); the rest of a ``step`` span is the wait for
    the device and the loop's bookkeeping. ``fault`` (tests only) wraps
    the step function to break it."""
    import jax

    make_batch = trainer_mod.synthetic_batch
    step_fn = trainer.step_fn

    def batch(*a, **kw):
        with jax.profiler.TraceAnnotation("batch"):
            return make_batch(*a, **kw)

    inner = fault(step_fn) if fault is not None else step_fn

    def dispatch(*a, **kw):
        with jax.profiler.TraceAnnotation("dispatch"):
            return inner(*a, **kw)

    trainer_mod.synthetic_batch = batch
    trainer.step_fn = dispatch
    try:
        yield
    finally:
        trainer_mod.synthetic_batch = make_batch
        trainer.step_fn = step_fn


def run_cell(name: str, wl: dict, cfgf: dict, seed: int, seconds: float,
             trace: bool, devices, t_start: float, fault=None) -> dict:
    """Everything after the look for a chip: set-up, window, check.
    Returns the result object (``checks`` last)."""
    import jax

    import repro.obs
    from bench import check, reference, scope_reduce
    from repro.launch.mesh import make_host_mesh
    from repro.train import trainer as trainer_mod

    chips = wl["chips"]
    clog = CompileLog()
    model, tcfg, data = build_program(wl, cfgf, seed, chips)
    mesh = make_host_mesh(data=chips)
    check_plan(model, tcfg, mesh)
    obs = repro.obs.configure(trace=True, set_as_default=False) \
        if trace else None
    trainer = trainer_mod.Trainer(model, tcfg, mesh, data, obs=obs)
    trainer.init_or_resume()
    op_scope = step_scopes(trainer) if trace else None
    log = trainer.log
    n_check = wl["check_steps"]
    with annotated(trainer_mod, trainer, fault):
        # set-up: the first steps, through the window's own call
        first = first_steps(trainer, n_check)
        # window
        tokens_per_step = data.global_batch * data.seq_len
        trace_dir = BENCH / "traces" / f"{name}.{seed}"
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        compiles_before = len(clog.names)
        steps, t0, t1 = window(trainer, seconds)
        setup_s = t0 - t_start
        if trace:
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            print(f"bench: trace written in "
                  f"{time.perf_counter() - t_stop:.1f} s", file=sys.stderr)
    window_compiles = clog.names[compiles_before:]
    clog.close()
    window_losses = log.losses[n_check:]
    failed = sum(not math.isfinite(x) for x in window_losses) + log.restarts
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    prog = readings(first)
    del first
    trainer.state = None
    del trainer
    gc.collect()

    metrics = {}
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    breakdown = None
    if not trace:
        metrics["tokens_per_s"] = {"value": steps * tokens_per_step
                                   / (t1 - t0), "unit": "tokens/s"}
        metrics["peak_hbm_gb"] = {"value": peak / 1e9, "unit": "GB"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    else:
        t_read = time.perf_counter()
        ev, spans = scope_reduce.load(
            str(sorted(trace_dir.glob("**/*.xplane.pb"))[-1]))
        red = scope_reduce.reduce_with_program(ev, spans, op_scope)
        del ev, spans
        print(f"bench: trace read and reduced in "
              f"{time.perf_counter() - t_read:.1f} s", file=sys.stderr)
        out_dir = BENCH / "out"
        out_dir.mkdir(exist_ok=True)
        (out_dir / f"{name}.{seed}.trace.json").write_text(
            json.dumps(red, indent=1))
        shutil.rmtree(trace_dir, ignore_errors=True)
        from bench.peaks import peak as peak_of

        facts = {"chips": chips, "steps": steps,
                 "tokens_per_step": tokens_per_step,
                 "flops_per_token": reference.arch_module(
                     cfgf["reference"]).flops_per_token(
                         cfgf["model"], wl["seq_len"])["total"],
                 "peak_flops_per_s": peak_of(devices[0].device_kind)[
                     "bf16_flops_per_s"],
                 "host_window_s": t1 - t0}
        for m in per_layer_metrics(name):
            v = metric_reader(m["name"])(red, facts)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev["busy_s"] = red.get("busy_s", 0.0)
        dev["window_s"] = red.get("window_s", 0.0)
        breakdown = {"device_ops": [[k, v] for k, v in
                                    list(red.get("op_s", {}).items())[:10]],
                     "idle_gaps": red.get("idle_gaps", [])[:10]}

    t_ref = time.perf_counter()
    ref = check.reference_readings(cfgf, wl, seed, devices)
    print(f"bench: reference in {time.perf_counter() - t_ref:.1f} s",
          file=sys.stderr)
    checks = check.compare(prog, ref, wl["limits"])
    checks["window_compiles"] = {"value": len(window_compiles), "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": steps, "failed": failed,
              "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    wl, cfgf = load_cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != wl["chips"]:
        print(f"bench: cell {args.workload} needs {wl['chips']} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    res = run_cell(args.workload, wl, cfgf, args.seed, args.seconds,
                   bool(args.trace), devices, T_START)
    for k, c in res["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
