"""End-to-end training driver: a ~100M-parameter LM with the full
production stack — SparCML Quantized-TopK gradient sync, WSD schedule,
ZeRO-1 optimizer sharding, checkpointing + automatic resume, straggler
watchdog, deterministic data pipeline.

    PYTHONPATH=src python examples/train_lm_topk.py --steps 300
    PYTHONPATH=src python examples/train_lm_topk.py --fast   # ~12M params
    PYTHONPATH=src python examples/train_lm_topk.py --fast --pipeline

--pipeline drives the non-blocking runtime (DESIGN.md §6) instead of the
synchronous Trainer.run: one-step-stale pipelined supersteps dispatched
asynchronously with background data prefetch. A short synchronous probe
runs first so the measured overlap win can be printed. Checkpoints are
interchangeable between the two loops.

A crash / Ctrl-C mid-run resumes from the latest checkpoint on restart
(same command). ~100M x 300 steps is a few hours on this small CPU
container; --fast demonstrates the identical code path in ~2 minutes.
"""
import argparse
import os
import tempfile

import jax.numpy as jnp

from repro.core.compressor import SyncConfig
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.config import ModelConfig
from repro.models.model import build_model
from repro.optim.optimizers import OptimizerConfig
from repro.optim.schedule import ScheduleConfig
from repro.train.state import TrainConfig
from repro.train.trainer import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--ckpt-dir", type=str, default=os.path.join(
        tempfile.gettempdir(), "sparcml_lm_ckpt"))
    ap.add_argument("--pipeline", action="store_true",
                    help="non-blocking runtime: pipelined stale-gradient "
                         "supersteps + async driver (DESIGN.md §6)")
    ap.add_argument("--superstep", type=int, default=4,
                    help="steps per scanned superstep (with --pipeline)")
    ap.add_argument("--zero", action="store_true",
                    help="ZeRO-sharded training state (DESIGN.md §11): the "
                         "gradient exchange stops at the owner shard "
                         "(scattered output mode, no allgather) and the "
                         "optimizer moments live on the owned chunks; "
                         "checkpoints interoperate with replicated runs")
    ap.add_argument("--adapt", action="store_true",
                    help="closed-loop re-planning (DESIGN.md §7): measured "
                         "per-bucket densities + calibrated alpha-beta "
                         "model re-select collective algorithms at drain "
                         "barriers (with --pipeline)")
    ap.add_argument("--trace", type=str, default=None, metavar="PATH",
                    help="export a Chrome-trace JSON of the run's "
                         "host spans (DESIGN.md §10)")
    ap.add_argument("--metrics-out", type=str, default=None, metavar="PATH",
                    help="write the metrics/event JSONL (per-bucket "
                         "nnz/wire histograms, plan swaps, step times) "
                         "and run a cost-model drift audit at the end")
    ap.add_argument("--blackbox", type=str, default=None, metavar="PATH",
                    help="attach the flight recorder (DESIGN.md §10.6): "
                         "a bounded ring of driver retires dumped to this "
                         "path on exception, watchdog fire, or SIGTERM/"
                         "SIGINT — the post-mortem for a killed run")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="chaos-injection smoke (DESIGN.md §12): run a "
                         "seed-derived FaultPlan of recoverable faults "
                         "(grad NaN/Inf, straggler, data stall, collective "
                         "raise, checkpoint corruption) against the "
                         "pipelined runtime; the run must complete via the "
                         "guarded step + retry/backoff recovery (implies "
                         "--pipeline)")
    args = ap.parse_args()
    enable_compile_cache()

    from repro import obs as obs_mod

    chaos = args.chaos is not None
    if chaos:
        args.pipeline = True  # guard/inject hooks live in the async driver
    obs = obs_mod.configure(trace=bool(args.trace),
                            metrics=bool(args.metrics_out) or bool(args.trace)
                            or chaos,
                            audit=bool(args.metrics_out),
                            recorder=args.blackbox or False)
    if obs.recorder is not None:
        obs.recorder.install_signal_handlers()

    if args.fast:
        cfg = ModelConfig(name="lm-12m", family="dense", num_layers=4,
                          d_model=256, num_heads=8, num_kv_heads=4, d_ff=512,
                          vocab_size=2048, dtype=jnp.float32,
                          param_dtype=jnp.float32, max_seq_len=256)
        data = DataConfig(global_batch=16, seq_len=128, vocab_size=2048)
        steps = min(args.steps, 60)
    else:
        # ~100M: 12 layers x d=768 (GPT-2-small-like with GQA + SwiGLU)
        cfg = ModelConfig(name="lm-100m", family="dense", num_layers=12,
                          d_model=768, num_heads=12, num_kv_heads=4,
                          d_ff=2048, vocab_size=32768, dtype=jnp.float32,
                          param_dtype=jnp.float32, max_seq_len=1024)
        data = DataConfig(global_batch=32, seq_len=512, vocab_size=32768)
        steps = args.steps

    model = build_model(cfg)
    print(f"model: {cfg.name}, {cfg.param_count()/1e6:.1f}M params")

    tcfg = TrainConfig(
        sync=SyncConfig(mode="sparcml", k_per_bucket=8, bucket_size=512,
                        algorithm="dsar_split_allgather", qsgd_bits=4,
                        min_sparse_size=65536, impl="ref",
                        output_mode="scattered" if args.zero else
                        "replicated"),
        optimizer=OptimizerConfig(kind="adamw"),
        schedule=ScheduleConfig(kind="wsd", peak_lr=6e-4, warmup_steps=20,
                                total_steps=steps),
        microbatches=2,
        zero1=True,
    )
    mesh = make_host_mesh()
    if args.zero:
        from repro.launch.dryrun import state_memory_breakdown

        mem = state_memory_breakdown(model, tcfg, mesh)
        print("zero: per-device state "
              + ", ".join(f"{k}={v/1e6:.1f}MB" for k, v in mem.items()))
    # shorter checkpoint cadence under chaos: the corrupt-then-restore
    # pair needs steps > 2*ckpt_every, and a CI-sized smoke (~30 steps)
    # should still cross several save boundaries
    ckpt_every = 10 if chaos else 25
    trainer = Trainer(model, tcfg, mesh, data, ckpt_dir=args.ckpt_dir,
                      ckpt_every=ckpt_every, obs=obs)
    start = trainer.init_or_resume()
    print(f"starting at step {start} (resume={'yes' if start else 'no'})")

    def med(times):
        return sorted(times)[len(times) // 2]

    injector = recovery = None
    if chaos:
        from repro.runtime.faults import (FaultInjector, FaultPlan,
                                          RecoveryConfig)

        plan = FaultPlan.chaos(args.chaos, steps, ckpt_every=ckpt_every)
        injector = FaultInjector(plan)
        recovery = RecoveryConfig(backoff_base_s=0.01, backoff_max_s=0.1)
        print("chaos plan (seed {}): ".format(args.chaos)
              + ", ".join(f"{s.kind}@{s.step}" for s in plan.specs))

    if args.pipeline:
        # short synchronous probe first, so the overlap win is measurable
        # (skipped under chaos: the probe loop has no recovery hooks)
        probe_to = start if chaos else min(start + 8, steps)
        if probe_to > start:
            trainer.run(probe_to)
        n_sync = len(trainer.log.step_times)
        # drop sync's first entry (it carries the jit compile); keep ALL
        # pipelined entries, compile included — the mean is exact in
        # aggregate (fill/drain intervals tile the run). Charging the
        # pipelined arm its own compile AND every checkpoint drain/save
        # (the short sync probe crosses no ckpt boundary) keeps the
        # printed win strictly conservative.
        sync_times = trainer.log.step_times[1:n_sync]
        log = trainer.run_pipelined(steps, staleness=1,
                                    superstep=args.superstep, depth=2,
                                    adapt=args.adapt, injector=injector,
                                    recovery=recovery)
        pipe_times = log.step_times[n_sync:]
        if sync_times and pipe_times:
            sync_avg = sum(sync_times) / len(sync_times)
            pipe_avg = sum(pipe_times) / len(pipe_times)
            print(f"overlap win: sync {sync_avg*1e3:.0f} ms/step -> "
                  f"pipelined {pipe_avg*1e3:.0f} ms/step "
                  f"({sync_avg/pipe_avg:.2f}x, staleness=1, "
                  f"superstep={args.superstep}, depth=2)")
        if args.adapt:
            print(f"adaptive re-planning: {len(log.plan_swaps)} plan "
                  f"swap(s)" + "".join(
                      f"\n  step {s}: {sig.split(',')[0]}..."
                      for s, sig in log.plan_swaps))
    else:
        log = trainer.run(steps)
    print(f"done: step {steps}, loss {log.losses[0]:.3f} -> {log.losses[-1]:.3f}, "
          f"avg step {sum(log.step_times)/len(log.step_times)*1e3:.0f} ms "
          f"(median {med(log.step_times)*1e3:.0f} ms), "
          f"restarts={log.restarts}, stragglers={len(log.straggler_events)}")
    if chaos:
        m = obs.metrics
        counters = {n: c.value for n, c in sorted(m.metrics.items())
                    if getattr(c, "kind", None) == "counter"
                    and n.startswith(("faults/", "recovery/", "guard/"))}
        print("chaos recovery: survived "
              f"{injector.fired_total} injected fault(s), "
              f"restarts={log.restarts}; "
              + " ".join(f"{n}={v}" for n, v in counters.items()))
        if injector.fired_total == 0:
            raise SystemExit("chaos: the plan injected nothing — seed/step "
                             "range mismatch, the smoke proved nothing")

    if obs.enabled:
        # drift audit: probe each distinct (algorithm, n, k) bucket of
        # the plan the run actually ended on, join against the cost
        # model's bucket_time prediction (DESIGN.md §10)
        plan = getattr(trainer, "last_plan", None)
        if obs.audit is not None and plan is not None:
            from repro.obs import audit_sync_plan

            audit_sync_plan(plan, mesh, axis_name="data",
                            net=getattr(trainer, "_net_cal", None),
                            auditor=obs.audit, registry=obs.metrics)
            print(obs.audit.summary())
        if obs.metrics_on:
            # compression-health verdict over the whole run (DESIGN.md
            # §10.5): EF growth, coverage floor, step-time p99 — reuse
            # the monitor the pipelined driver evaluated at drains
            from repro.obs import HealthMonitor

            mon = trainer.last_health or HealthMonitor(
                obs.metrics, audit=obs.audit)
            mon.evaluate()
            print("health:", mon.summary())
        obs.export(trace_path=args.trace, metrics_path=args.metrics_out)
        if obs.metrics_on:
            print(obs.metrics.summary())
        for p in (args.trace, args.metrics_out):
            if p:
                print(f"obs: wrote {p}")


if __name__ == "__main__":
    main()
