"""Trace a cell's window with the program's own spans and scopes on, and
price the program's tracer.

    python bench/tools/scope_trace.py --workload <cell> --seeds <n> ... \\
        [--seconds 10] [--cost-pairs 0] [--out chiprun_out/scope_trace]

For each seed, in one process: build the cell's program as
``bench/run.py`` does, with ``Trainer(obs=repro.obs.configure(trace=True,
set_as_default=False))``; for the first seed, take the step's
``{instruction: scope}`` map from the text of a compile of its own (see
:func:`step_text`); drive its first ``check_steps`` steps; then trace a
window of ``--seconds`` under ``jax.profiler``, with
the benchmark's own ``step``, ``batch`` and ``dispatch`` spans around the
same calls, and reduce it with ``bench/scope_reduce.py``. One JSON line
per seed on stdout: the readings of ``model_ms``, ``compress_ms``,
``qsgd_ms``, ``update_ms``, ``host_step_ms`` and of the benchmark's
per-layer metrics, ``unscoped_s`` and the ops it holds, the idle gaps and
the sort ops per step; the whole reduction goes to
``<out>/<cell>.<seed>.json``.

``--cost-pairs n`` then runs 2n untraced windows, the tracer off and on
in turn (off, on, on, off, ...), and reports tokens/s of each: what the
program's spans cost with the profiler off.

Like ``bench/run.py`` it refuses (exit 2) unless JAX sees as many TPU
chips as the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import re
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

NEW_METRICS = ("model_ms", "compress_ms", "qsgd_ms", "update_ms",
               "host_step_ms")
BENCH_METRICS = ("device_idle_pct", "mfu", "collective_ms",
                 "exposed_collective_ms")
SORT_OP = re.compile(r"^(sort|top_k|topk|top-k)\b", re.I)


def window(trainer, seconds: float) -> tuple[int, float]:
    """The benchmark's window: ``Trainer.run(step + 1)`` under a ``step``
    span until ``seconds`` have passed; (steps, host seconds)."""
    import jax

    t0 = time.perf_counter()
    steps = 0
    while True:
        with jax.profiler.TraceAnnotation("step"):
            trainer.run(int(trainer.state.step) + 1)
        steps += 1
        if time.perf_counter() - t0 >= seconds:
            return steps, time.perf_counter() - t0


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


def trace_seed(name: str, wl: dict, cfgf: dict, seed: int, seconds: float,
               cost_pairs: int, devices, out: Path,
               op_scope: dict | None = None) -> dict:
    """One seed's traced window (and tracer-cost windows); ``op_scope``
    is the step's map from an earlier seed of the same cell, or None to
    take it here."""
    import jax

    import repro.obs
    from bench import run as bench_run
    from bench import scope_reduce, trace_reduce
    from bench.flops import flops_per_token
    from bench.peaks import peak as peak_of
    from repro.data.pipeline import synthetic_batch
    from repro.launch.mesh import make_host_mesh
    from repro.obs.scopes import op_scopes
    from repro.train import trainer as trainer_mod

    chips = wl["chips"]
    model, tcfg, data = bench_run.build_program(wl, cfgf, seed, chips)
    mesh = make_host_mesh(data=chips)
    bench_run.check_plan(model, tcfg, mesh)
    traced = repro.obs.configure(trace=True, set_as_default=False)
    trainer = trainer_mod.Trainer(model, tcfg, mesh, data, obs=traced)
    trainer.init_or_resume()
    if op_scope is None:
        # the arguments of the trainer's first call, as it makes them
        batch = jax.tree.map(jax.numpy.asarray, synthetic_batch(data, 0))
        key = jax.random.fold_in(jax.random.PRNGKey(tcfg.seed), 0)
        with mesh:
            op_scope = op_scopes(step_text(trainer.step_fn, trainer.state,
                                           batch, key))
    tokens_per_step = data.global_batch * data.seq_len
    with bench_run.annotated(trainer_mod, trainer):
        trainer.run(wl["check_steps"])
        trace_dir = out / f"xplane.{name}.{seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        steps, host_s = window(trainer, seconds)
        jax.profiler.stop_trace()
        cost = []
        for i in range(2 * cost_pairs):
            on = i % 4 in (1, 2)
            trainer.obs = traced if on else repro.obs.Observability()
            traced.tracer.clear()
            n, secs = window(trainer, seconds)
            cost.append({"tracer": on, "steps": n,
                         "tokens_per_s": n * tokens_per_step / secs})
    path = str(sorted(trace_dir.glob("**/*.xplane.pb"))[-1])
    ev = trace_reduce.load(path)
    red = scope_reduce.reduce_with_program(
        ev, scope_reduce.load_program_spans(path), op_scope)
    shutil.rmtree(trace_dir, ignore_errors=True)
    facts = {"chips": chips, "steps": steps,
             "tokens_per_step": tokens_per_step,
             "flops_per_token": flops_per_token(
                 cfgf["model"], wl["seq_len"])["total"],
             "peak_flops_per_s": peak_of(devices[0].device_kind)[
                 "bf16_flops_per_s"] if devices[0].platform == "tpu" else 1.0,
             "host_window_s": host_s}
    metrics = {}
    for m in NEW_METRICS + BENCH_METRICS:
        v = bench_run.metric_reader(m)(red, facts)
        if v is not None:
            metrics[m] = v
    sorts = {op: s for op, s in red.get("op_s", {}).items()
             if SORT_OP.match(op)}
    sort_ms: dict[str, float] = {}
    for op, secs in sorts.items():
        where = op_scope.get(op, "none")
        sort_ms[where] = sort_ms.get(where, 0.0) + 1e3 * secs / steps
    unscoped = [[op, 1e3 * s / steps] for op, s in red.get("op_s", {}).items()
                if op not in op_scope][:10]
    (out / f"{name}.{seed}.json").write_text(json.dumps(
        {"op_scope_counts": _counts(op_scope), **red}, indent=1))
    return {"cell": name, "seed": seed, "steps": steps,
            "metrics": metrics,
            "busy_s": red.get("busy_s"), "window_s": red.get("window_s"),
            "scope_s": red.get("scope_s"),
            "unscoped_s": red.get("unscoped_s"),
            "unscoped_top_ms": unscoped,
            "host_span_s": red.get("host_span_s"),
            "host_span_n": red.get("host_span_n"),
            "sort_ms_by_scope": sort_ms,
            "sort_ops_top_ms": sorted(
                ([op, 1e3 * s / steps] for op, s in sorts.items()),
                key=lambda kv: -kv[1])[:4],
            "idle_gaps": red.get("idle_gaps"),
            "tracer_cost": cost,
            "device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices)},
            "op_scope": op_scope}


def _counts(op_scope: dict) -> dict:
    out: dict[str, int] = {}
    for s in op_scope.values():
        out[s] = out.get(s, 0) + 1
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--cost-pairs", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" /
                                         "scope_trace"))
    args = ap.parse_args(argv)

    from bench.run import load_cell

    wl, cfgf = load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != wl["chips"]:
        print(f"scope_trace: cell {args.workload} needs {wl['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              "device(s)", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    op_scope = None
    for seed in args.seeds:
        res = trace_seed(args.workload, wl, cfgf, seed, args.seconds,
                         args.cost_pairs, devices, out, op_scope)
        op_scope = res.pop("op_scope")
        print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
