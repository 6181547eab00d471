"""Serving demo: static batched decode (the PR-0 reference engine) or —
with ``--continuous`` — the continuous-batching scheduler driving the
slot decode engine with plan-driven sparse MoE dispatch (DESIGN.md §8).

    PYTHONPATH=src python examples/serve_decode.py                # static
    PYTHONPATH=src python examples/serve_decode.py --continuous   # scheduler
    PYTHONPATH=src python examples/serve_decode.py --fast --continuous

``--continuous`` runs a Poisson arrival trace of ragged-prompt requests
through the adaptive engine and prints throughput, wire bytes, and the
sparse<->dense dispatch swaps the telemetry drove.
"""
import argparse
import time

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.config import ModelConfig
from repro.models.model import build_model
from repro.serve import (
    ContinuousServeEngine,
    Request,
    ServeConfig,
    ServeEngine,
    poisson_trace,
)

import jax.numpy as jnp


def build(fast: bool):
    # experts over a 2-wide 'model' axis where the device count allows
    mesh = make_host_mesh(model=2 if len(jax.devices()) % 2 == 0 else 1)
    kw = dict(num_layers=2, d_model=128, d_ff=256) if fast else \
        dict(num_layers=4, d_model=256, d_ff=512)
    cfg = ModelConfig(name="serve-demo", family="moe", num_heads=8,
                      num_kv_heads=4, vocab_size=2048, dtype=jnp.float32,
                      param_dtype=jnp.float32, max_seq_len=256,
                      num_experts=4, experts_per_token=2,
                      moe_d_ff=kw["d_ff"] // 2, capacity_factor=4.0, **kw)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return mesh, model, params


def run_static(mesh, model, params, batch: int, tokens: int, obs=None):
    engine = ServeEngine(model, mesh, params, cache_len=128, batch_size=batch,
                         obs=obs)
    prompts = np.random.default_rng(0).integers(
        0, 2048, (batch, 16)).astype(np.int32)
    t0 = time.perf_counter()
    out = engine.generate(prompts, max_new_tokens=tokens)
    dt = time.perf_counter() - t0
    print(f"static: {out.shape} tokens for {batch} requests in {dt:.2f}s "
          f"({out.size / dt:.0f} tok/s on emulated CPU devices)")
    print("first request:", out[0].tolist())
    out2 = engine.generate(prompts, max_new_tokens=tokens)
    assert np.array_equal(out, out2)
    print("greedy decode is deterministic: OK")


def run_continuous(mesh, model, params, batch: int, tokens: int, obs=None,
                   slo: "ServeConfig | None" = None, injector=None):
    rng = np.random.default_rng(0)
    n_req = 2 * batch
    arrivals = poisson_trace(n_req, rate=0.5, seed=0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, 2048, int(rng.integers(4, 20))),
                    max_new_tokens=int(rng.integers(tokens // 2, tokens + 1)),
                    arrival=float(arrivals[i]))
            for i in range(n_req)]
    engine = ContinuousServeEngine(model, mesh, params, cache_len=128,
                                   batch_size=batch, dispatch="adaptive",
                                   obs=obs, serve_cfg=slo, injector=injector)
    res = engine.run(reqs)
    occ = [r["active"] for r in res.step_log]
    print(f"continuous: {len(reqs)} requests, {res.tokens} tokens in "
          f"{res.decode_steps} decode steps / {res.wall_s:.2f}s "
          f"({res.tok_per_s:.0f} tok/s; occupancy {min(occ)}..{max(occ)} "
          f"of {batch} slots)")
    print(f"dispatch wire: {res.wire_bytes / 1e3:.1f} kB modeled; "
          f"plan swaps: {[(s['step'], s['reason'], s['signature']) for s in res.swap_log]}")
    if res.latency:
        lat = res.latency
        print("latency (decode-step units): "
              f"ttft p50={lat['ttft']['p50']:.1f} p99={lat['ttft']['p99']:.1f}; "
              f"tpot p50={lat['tpot']['p50']:.2f}; "
              f"e2e p99={lat['e2e']['p99']:.1f}")
    if slo is not None and obs is not None and obs.metrics_on:
        # res.health only carries verdicts when the registry was live
        misses = [(e.severity, e.subject) for e in res.health]
        print(f"SLO targets {slo.slo_targets()}: "
              + (f"{len(misses)} miss(es) {misses}" if misses
                 else "all attained"))
    # under load shedding (queue_limit / shed deadline) a request may be
    # retired via the shed list instead of outputs; every request must
    # still be accounted for exactly once
    assert len(res.outputs) + len(res.shed) == n_req
    if res.shed:
        print(f"load shed: {len(res.shed)} request(s) {sorted(res.shed)}")
    if injector is not None:
        retries = obs.metrics.counter("serve/retries").value if (
            obs is not None and obs.metrics_on) else 0
        print("chaos recovery: survived "
              f"{injector.fired_total} injected fault(s), "
              f"tick retries={retries}, shed={len(res.shed)}")
        if injector.fired_total == 0:
            raise SystemExit("chaos: the plan injected nothing — seed/step "
                             "range mismatch, the smoke proved nothing")
    else:
        print("all requests completed: OK")
    return engine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller model + fewer tokens (CI smoke)")
    ap.add_argument("--batch", type=int, default=8,
                    help="decode slots (continuous) / batch size (static)")
    ap.add_argument("--tokens", type=int, default=None,
                    help="max new tokens per request")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching + adaptive sparse dispatch")
    ap.add_argument("--trace", type=str, default=None, metavar="PATH",
                    help="export a Chrome-trace JSON of the run "
                         "(prefill/decode/admit spans, DESIGN.md §10)")
    ap.add_argument("--metrics-out", type=str, default=None, metavar="PATH",
                    help="write the metrics/event JSONL (occupancy/queue/"
                         "wire histograms, latency percentiles, plan "
                         "swaps) and run a serve-plan drift audit")
    ap.add_argument("--slo-ttft", type=float, default=16.0,
                    help="p99 time-to-first-token target in decode-step "
                         "units (DESIGN.md §10.5); misses become ranked "
                         "health/serve_slo events")
    ap.add_argument("--slo-e2e", type=float, default=96.0,
                    help="p99 arrival->retirement target in decode-step "
                         "units")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="chaos-injection smoke (DESIGN.md §12): a "
                         "seed-derived FaultPlan of recoverable serve "
                         "faults (collective raise, straggler, pipeline "
                         "stall) against the decode loop; the run must "
                         "complete via pre-dispatch tick retries "
                         "(implies --continuous)")
    args = ap.parse_args()
    enable_compile_cache()
    tokens = args.tokens if args.tokens is not None else (8 if args.fast else 24)

    from repro import obs as obs_mod

    chaos = args.chaos is not None
    if chaos:
        args.continuous = True  # tick retry/shed hooks live in the scheduler
    obs = obs_mod.configure(trace=bool(args.trace),
                            metrics=bool(args.metrics_out) or bool(args.trace)
                            or chaos,
                            audit=bool(args.metrics_out))
    mesh, model, params = build(args.fast)
    injector = None
    if chaos:
        from repro.runtime.faults import FaultInjector, FaultPlan

        # recoverable serve classes only: nonfinite/sigterm abort a
        # decode run by design (donated state cannot be replayed)
        plan = FaultPlan.chaos(args.chaos, 16,
                               classes=("collective", "straggler", "stall"))
        injector = FaultInjector(plan)
        print("chaos plan (seed {}): ".format(args.chaos)
              + ", ".join(f"{s.kind}@tick{s.step}" for s in plan.specs))
    engine = None
    if args.continuous:
        slo = ServeConfig(slo_ttft_p99=args.slo_ttft,
                          slo_e2e_p99=args.slo_e2e)
        engine = run_continuous(mesh, model, params, args.batch, tokens,
                                obs=obs, slo=slo, injector=injector)
    else:
        run_static(mesh, model, params, args.batch, tokens, obs=obs)

    if obs.enabled:
        plan = getattr(engine, "_plan", None) if engine is not None else None
        if obs.audit is not None and plan is not None:
            from repro.obs import audit_serve_plan

            # probe each activation bucket of the plan the engine ended
            # on and join against bucket_time (DESIGN.md §10)
            audit_serve_plan(plan, mesh, axis_name="model",
                             auditor=obs.audit, registry=obs.metrics)
            print(obs.audit.summary())
        obs.export(trace_path=args.trace, metrics_path=args.metrics_out)
        if obs.metrics_on:
            print(obs.metrics.summary())
        for p in (args.trace, args.metrics_out):
            if p:
                print(f"obs: wrote {p}")


if __name__ == "__main__":
    main()
