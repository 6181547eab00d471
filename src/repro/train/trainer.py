"""Fault-tolerant training driver.

Responsibilities beyond the jitted step:
  * checkpoint every N steps (atomic) + resume-from-latest on start,
  * survive injected/step failures: restore last checkpoint and continue
    (the data pipeline is keyed by step, so replayed batches are identical),
  * straggler watchdog: per-step wall time vs a running median; a step
    exceeding ``straggler_factor`` x median is logged and counted — on a
    real pod this feeds the skip/backup-worker policy; in-process it is
    observability (SPMD has no per-host stragglers to act on),
  * elastic restart: `resume(new_mesh)` re-chunks replica-dependent state
    (see checkpoint.restore(remesh=True)).

Two main loops: :meth:`Trainer.run` (synchronous reference — dispatch one
step, block on its loss) and :meth:`Trainer.run_pipelined` (non-blocking
runtime, DESIGN.md §6 — pipelined stale-gradient supersteps driven by the
double-buffered async driver in ``repro/runtime``). Checkpoints written
by either loop are interchangeable: the pipelined loop strips the
in-flight bucket buffers before saving and re-attaches zeros on resume.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Optional

import jax
import numpy as np

from repro.data.pipeline import DataConfig, synthetic_batch
from repro.obs import resolve as _resolve_obs
from repro.runtime.driver import DriverLog, record_step
from repro.train import checkpoint as ckpt
from repro.train.state import TrainConfig, TrainState
from repro.train.train_step import build_train_step, dp_total_of, init_state

# One log type for both loops (registry-backed, DESIGN.md §10); the name
# survives for PR-2 callers that import TrainerLog.
TrainerLog = DriverLog


class Trainer:
    def __init__(self, model, tcfg: TrainConfig, mesh, data_cfg: DataConfig,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
                 straggler_factor: float = 3.0, obs=None):
        self.model = model
        self.tcfg = tcfg
        self.mesh = mesh
        self.data_cfg = data_cfg
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.straggler_factor = straggler_factor
        self.obs = _resolve_obs(obs)
        self.log = TrainerLog(
            registry=self.obs.metrics if self.obs.metrics_on else None)
        self.step_fn, (self.shapes, self.specs) = build_train_step(model, tcfg, mesh)
        self.state: Optional[TrainState] = None
        self._root_key = jax.random.PRNGKey(tcfg.seed)
        # the AdaptiveRuntime of the most recent run_pipelined(adapt=...)
        # call (None otherwise) — exposes the active plan for
        # inspection/tests; the checkpoint meta is the durable record
        self.last_adapt_runtime = None
        # the SyncPlan the most recent run_pipelined compiled against
        # (adaptive runs: the plan active at exit) — what the examples
        # hand to obs.audit_sync_plan after the run
        self.last_plan = None
        # the HealthMonitor of the most recent metrics-on run_pipelined
        # (None otherwise) — its .history holds the ranked verdicts
        self.last_health = None

    # -- lifecycle ---------------------------------------------------------
    def init_or_resume(self):
        self.state, _ = init_state(self.model, self.tcfg, self.mesh)
        if self.ckpt_dir and ckpt.latest_step(self.ckpt_dir) is not None:
            self.state = self._restore_any_layout()
            self.log.restarts += 1
        return int(self.state.step)

    def _restore_any_layout(self):
        """Restore the latest checkpoint, converting the optimizer state
        when it was written under the OTHER ZeRO layout (DESIGN.md §11):
        restore into a template of the checkpoint's own layout, then map
        the moments through the full canonical buffer — value-exact, so a
        scattered run resumes a replicated checkpoint (and vice versa)
        with identical per-coordinate optimizer state."""
        import dataclasses

        from repro.train import train_step as ts

        dp_total = dp_total_of(self.mesh)
        my_layout = ckpt.opt_layout_of(self.tcfg)
        step = self._verified_step()
        meta = ckpt.load_meta(self.ckpt_dir, step)
        ck_layout = meta.get("opt_layout", my_layout)
        if ck_layout == my_layout:
            return ckpt.restore(self.ckpt_dir, self.state, dp_total=dp_total,
                                step=step, verify=True)
        other_mode = {"zero_scattered": "scattered",
                      "zero1_leaf": "replicated"}.get(ck_layout)
        if other_mode is None or my_layout == "full":
            raise ValueError(
                f"checkpoint opt layout {ck_layout!r} is not resumable "
                f"under {my_layout!r} (only zero1_leaf <-> zero_scattered)")
        other_tcfg = dataclasses.replace(
            self.tcfg,
            sync=dataclasses.replace(self.tcfg.sync, output_mode=other_mode))
        other_shapes, _, _ = ts.state_shapes(self.model, other_tcfg,
                                             self.mesh, return_plan=True)
        restored = ckpt.restore(self.ckpt_dir, other_shapes,
                                dp_total=dp_total, step=step, verify=True)
        _, _, plan = ts.state_shapes(self.model, self.tcfg, self.mesh,
                                     return_plan=True)
        return ckpt.convert_opt_layout(restored, plan, source=ck_layout,
                                       target=my_layout)

    def _verified_step(self) -> int:
        """The restore target under the integrity policy (DESIGN.md
        §12.4): the newest checkpoint that passes CRC verification.
        Falling back past a corrupt newest checkpoint is a
        ``recovery/ckpt_fallback`` event; nothing verifying is a clean
        abort (CheckpointCorrupt)."""
        newest = ckpt.latest_step(self.ckpt_dir)
        step = ckpt.latest_valid_step(self.ckpt_dir)
        if step is None:
            raise ckpt.CheckpointCorrupt(
                f"no checkpoint under {self.ckpt_dir} passes CRC "
                "verification (retention window exhausted)")
        if step != newest:
            self.obs.event("recovery/ckpt_fallback", step=step,
                           corrupt_step=newest)
            if self.obs.metrics_on:
                self.obs.metrics.counter("recovery/ckpt_fallbacks").inc()
        return step

    def resume_elastic(self, new_mesh):
        """Elastic restart onto a different mesh (pod count change)."""
        self.mesh = new_mesh
        self.step_fn, (self.shapes, self.specs) = build_train_step(
            self.model, self.tcfg, new_mesh)
        self.state, _ = init_state(self.model, self.tcfg, new_mesh)
        if self.ckpt_dir and ckpt.latest_step(self.ckpt_dir) is not None:
            self.state = ckpt.restore(
                self.ckpt_dir, self.state, dp_total=dp_total_of(new_mesh),
                remesh=True)
        return int(self.state.step)

    # -- main loop ---------------------------------------------------------
    def run(self, num_steps: int, fail_at: Optional[int] = None) -> TrainerLog:
        """Train for num_steps (absolute). fail_at injects a fault for tests.

        Traced (``obs`` with tracing on), the call is one ``train/run``
        span; each step's ``train/batch`` (rows, their transfer, the step
        key), ``train/dispatch`` (the jitted call), ``train/wait`` (until
        the loss is ready), ``train/log`` (the loss read and
        ``record_step``) and ``train/checkpoint`` nest in it. What
        ``train/run`` holds beyond them is the loop's own bookkeeping,
        the reads of ``state.step`` among it (DESIGN.md §10.1)."""
        span = self.obs.span
        with span("train/run", num_steps=num_steps):
            if self.state is None:
                self.init_or_resume()
            if self.state.inflight is not None:
                # hand-off from a pipelined run: drop the in-flight
                # reduction (one step of gradients — the documented
                # lossy-accumulator deal, same as the EF reset on
                # elastic restarts)
                self.state = self.state._replace(inflight=None)
            with self.mesh:
                while int(self.state.step) < num_steps:
                    step = int(self.state.step)
                    with span("train/batch", step=step):
                        batch = jax.tree.map(
                            jax.numpy.asarray,
                            synthetic_batch(self.data_cfg, step))
                        key = jax.random.fold_in(self._root_key, step)
                    t0 = time.perf_counter()
                    try:
                        if fail_at is not None and step == fail_at:
                            fail_at = None  # fail exactly once
                            raise RuntimeError("injected node failure")
                        with span("train/dispatch", step=step):
                            new_state, metrics = self.step_fn(
                                self.state, batch, key)
                        with span("train/wait", step=step):
                            jax.block_until_ready(metrics["loss"])
                    except Exception:
                        # node-failure path: restore + replay
                        if not self.ckpt_dir:
                            raise
                        self.log.restarts += 1
                        self.state = ckpt.restore(
                            self.ckpt_dir, self._abstract_like(),
                            dp_total=dp_total_of(self.mesh))
                        continue
                    dt = time.perf_counter() - t0
                    self.state = new_state
                    with span("train/log", step=step):
                        record_step(self.log, step, dt,
                                    float(metrics["loss"]),
                                    self.straggler_factor)
                    if self.ckpt_dir and (step + 1) % self.ckpt_every == 0:
                        self._save()
            if self.ckpt_dir:
                self._save()
        return self.log

    def _save(self) -> None:
        with self.obs.span("train/checkpoint"):
            ckpt.save(self.ckpt_dir, self.state,
                      dp_total=dp_total_of(self.mesh),
                      opt_layout=ckpt.opt_layout_of(self.tcfg))

    # -- non-blocking runtime (DESIGN.md §6/§7) ----------------------------
    def run_pipelined(self, num_steps: int, *, staleness: int = 1,
                      superstep: int = 4, depth: int = 2,
                      prefetch: int = 2, unroll: bool = False,
                      adapt=False, guard: bool = True, injector=None,
                      recovery=None) -> TrainerLog:
        """Train for num_steps (absolute) with the pipelined runtime:
        K-step scanned supersteps (stale-gradient overlap, ``staleness``
        in {0, 1}) dispatched ``depth`` deep by the async host driver,
        with background data prefetch. Logging and checkpoints sync only
        on retired steps; checkpoints store the synchronous state shape
        (in-flight buffers stripped), so sync and pipelined runs resume
        from each other's checkpoints.

        ``adapt`` (False | True | runtime.adapt.AdaptConfig) turns on
        closed-loop re-planning (DESIGN.md §7): per-bucket measured
        densities feed the calibrated cost model, and accepted replans
        swap the compiled superstep at drain barriers. Checkpoints then
        carry the active plan signature + algorithm map, so a restart
        resumes the ADAPTED plan.

        Fault tolerance (DESIGN.md §12): ``guard=True`` (default) builds
        the GUARDED step — non-finite gradients skip the apply with EF
        residuals and optimizer state preserved exactly, and escalate to
        a checkpoint rewind after N consecutive trips. ``recovery`` (a
        ``runtime.faults.RecoveryConfig``) bounds the driver's restore
        loop with per-fault-class retry budgets + jittered backoff.
        ``injector`` (a ``runtime.faults.FaultInjector``) runs the chaos
        plan against this run: grad-leaf NaN/Inf via the batch-carried
        fault vector, prefetch stalls, collective raises, stragglers,
        post-save checkpoint corruption, SIGTERM."""
        from repro.data.pipeline import synthetic_batch
        from repro.runtime import adapt as rt_adapt
        from repro.runtime import driver as rt_driver
        from repro.runtime import pipeline as rt_pipeline

        if self.state is None:
            self.init_or_resume()
        inject = injector is not None
        if inject:
            # the injector's grad-flag vector is indexed by grad leaf
            # (== param leaf) order — the same flatten the step body uses
            injector.bind(
                n_leaves=len(jax.tree_util.tree_leaves(self.state.params)))

        runtime = None
        plan0 = None
        if adapt:
            from repro.train import train_step as ts

            if staleness < 1:
                raise ValueError("adaptive re-planning rides the pipelined "
                                 "runtime: needs staleness >= 1")
            acfg = (adapt if isinstance(adapt, rt_adapt.AdaptConfig)
                    else rt_adapt.AdaptConfig())
            _, _, base_plan = ts.state_shapes(self.model, self.tcfg,
                                              self.mesh, return_plan=True)
            plan0 = base_plan
            if self.ckpt_dir and ckpt.latest_step(self.ckpt_dir) is not None:
                meta = ckpt.load_meta(self.ckpt_dir)
                algos = meta.get("plan_algorithms")
                if algos:
                    plan0 = base_plan.replan(
                        algorithms=algos,
                        pod_sparse=meta.get("plan_pod_sparse"))
            runtime = rt_adapt.AdaptiveRuntime(
                self.model, self.tcfg, self.mesh, plan=plan0,
                net=self._calibrated_net(acfg), cfg=acfg,
                staleness=staleness, superstep=superstep, unroll=unroll,
                obs=self.obs, guard=guard, inject=inject)
            self.last_adapt_runtime = runtime
            fn, plan = runtime.current_fn(), runtime.current_plan
        else:
            # no controller to consume stats: compile the telemetry in
            # only when a metrics registry will record it (off = the
            # PR-2 step, byte-identical jaxpr)
            telemetry = self.obs.metrics_on
            if superstep > 1:
                fn, _, plan = rt_pipeline.build_superstep(
                    self.model, self.tcfg, self.mesh, staleness=staleness,
                    steps=superstep, unroll=unroll, telemetry=telemetry,
                    guard=guard, inject=inject)
            else:
                fn, _, plan = rt_pipeline.build_pipelined_step(
                    self.model, self.tcfg, self.mesh, staleness=staleness,
                    telemetry=telemetry, guard=guard, inject=inject)
            if telemetry:
                runtime = rt_adapt.TelemetryObserver(self.obs)
        state = self.state
        if staleness:
            state = rt_pipeline.attach_inflight(state, plan, self.mesh)
        elif state.inflight is not None:
            state = state._replace(inflight=None)

        dp_total = dp_total_of(self.mesh)

        def ckpt_fn(s):
            extra = None
            active = getattr(runtime, "current_plan", None)
            if active is not None:
                extra = {"plan_signature": active.signature(),
                         "plan_version": active.version,
                         "plan_algorithms": active.algorithms(),
                         "plan_pod_sparse": active.pod_sparse_flags()}
            ckpt.save(self.ckpt_dir, s._replace(inflight=None),
                      dp_total=dp_total, extra_meta=extra,
                      opt_layout=ckpt.opt_layout_of(self.tcfg))
            if inject:
                # chaos hook: a scheduled ckpt_corrupt spec flips bytes
                # in the save that just landed; the CRC fallback below
                # is what must survive it
                injector.corrupt_checkpoint(self.ckpt_dir, int(s.step))

        def restore_fn():
            restored = ckpt.restore(
                self.ckpt_dir,
                self._abstract_like()._replace(inflight=None),
                dp_total=dp_total, step=self._verified_step(), verify=True)
            if staleness:
                restored = rt_pipeline.attach_inflight(restored, plan,
                                                       self.mesh)
            return restored

        health = None
        if self.obs.metrics_on:
            # compression-health rules over the run's registry: EF-norm
            # growth / mass-coverage floor on the executor's mass
            # telemetry, step-time p99 regression on the driver series;
            # evaluated at drain barriers + end of run (DESIGN.md §10.5)
            from repro.obs.health import HealthMonitor

            health = HealthMonitor(self.obs.metrics,
                                   audit=getattr(self.obs, "audit", None))
            self.last_health = health

        with self.mesh:
            state, _ = rt_driver.run_pipelined(
                fn, state,
                start_step=int(state.step), num_steps=num_steps,
                batch_fn=lambda step: synthetic_batch(self.data_cfg, step),
                key_fn=lambda step: jax.random.fold_in(self._root_key, step),
                cfg=rt_driver.DriverConfig(depth=depth, prefetch=prefetch,
                                           steps_per_unit=superstep),
                log=self.log, straggler_factor=self.straggler_factor,
                ckpt_every=self.ckpt_every if self.ckpt_dir else None,
                ckpt_fn=ckpt_fn if self.ckpt_dir else None,
                restore_fn=restore_fn if self.ckpt_dir else None,
                adapt=runtime,
                obs=self.obs, health=health,
                recovery=recovery, injector=injector,
            )
        self.state = state
        self.last_plan = getattr(runtime, "current_plan", None) or plan
        if self.ckpt_dir:
            ckpt_fn(self.state)
        return self.log

    def _calibrated_net(self, acfg):
        """One-shot alpha-beta calibration, cached per Trainer (the fit is
        cheap but not free; the network does not change mid-process)."""
        from repro.core.cost_model import DEFAULT_NET

        if not acfg.calibrate:
            return DEFAULT_NET
        if getattr(self, "_net_cal", None) is None:
            from repro.utils.calibrate import calibrate

            # the auditor (when attached) receives the post-fit ladder
            # residuals as algorithm "dense_ladder" — the calibrator's
            # own quality signal (DESIGN.md §10)
            self._net_cal = calibrate(self.mesh,
                                      auditor=getattr(self.obs, "audit", None))
        return self._net_cal

    def _abstract_like(self):
        if self.state is not None:
            return self.state
        state, _ = init_state(self.model, self.tcfg, self.mesh)
        return state
