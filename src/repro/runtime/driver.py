"""Double-buffered non-blocking host driver (DESIGN.md §6).

``Trainer.run`` dispatches one step, then blocks on its loss before
dispatching the next — the host round-trip serializes with the device.
This driver replaces that with a dispatch WINDOW:

  * **async dispatch** — up to ``depth`` units (steps, or K-step
    supersteps) are dispatched before the oldest is retired; JAX's async
    dispatch turns the returned arrays into futures, so the device queue
    stays full while the host prepares the next batch;
  * **data prefetch** — batch generation (the host-side cost) runs in a
    background thread ``prefetch`` units ahead of dispatch;
  * **retire-only syncing** — logging reads (loss, step time) block only
    on the unit leaving the window; checkpoints first drain the window,
    so the save reads a fully retired state (and the caller strips the
    in-flight bucket buffers — see TrainState.inflight).

Step times are retire-to-retire wall intervals divided by the unit's step
count: with the window full, that IS the steady-state per-step cost, with
dispatch overhead and data generation amortized/overlapped. The
attribution is exact in aggregate (the intervals tile the run), but
pipeline fill inflates the first interval and the final drain deflates
the last ones. The ONE summary statistic for ``log.step_times`` is
therefore the ROLLING MEDIAN of the last ``STRAGGLER_WINDOW`` steps —
robust to those fill/drain transients — and it is what the straggler
watchdog compares against (``record_step``), what the
``driver/straggler_median_s`` gauge exports, and what consumers should
read; the mean is only exact for whole-run aggregates.

Observability (DESIGN.md §10): ``run_pipelined`` takes an ``obs`` handle
(``repro.obs``). Host spans wrap dispatch/retire/drain/checkpoint (and,
under a ``jax.profiler`` trace, land beside the device's ops), plan
swaps and restarts become structured events. All of it is host-side:
with observability off the loop is byte-identical, and with it on,
retire remains the only ``block_until_ready`` (tests/test_obs.py pins
both properties).

The driver is state-linear (step functions donate their input state), so
after a dispatch only the returned state is live; on failure the window
is discarded and ``restore_fn`` supplies a replayable state (the data
pipeline is keyed by step, so replayed batches are identical).
"""
from __future__ import annotations

import queue
import threading
import time
from collections import deque
from dataclasses import dataclass
from statistics import median
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import resolve as _resolve_obs
from repro.obs.metrics import MetricsRegistry
from repro.runtime.faults import (
    NonFiniteEscalation,
    PrefetchStalled,
    RecoveryConfig,
    RetrySupervisor,
)

# Rolling window (in steps) of the documented step-time statistic: the
# median over this window is THE summary of ``log.step_times`` — used by
# the straggler watchdog, exported as ``driver/straggler_median_s``.
STRAGGLER_WINDOW = 50
# Minimum retired steps before the watchdog trusts the median at all.
STRAGGLER_WARMUP = 5


@dataclass(frozen=True)
class DriverConfig:
    depth: int = 2          # dispatched-but-unretired units (double-buffered)
    prefetch: int = 2       # units of host batches prepared ahead
    steps_per_unit: int = 1 # K of the scanned superstep fn (1 = plain step)
    # Bound on waiting for the prefetch thread before declaring the data
    # pipeline stalled (PrefetchStalled -> the recovery path). Generous:
    # batch generation is milliseconds; only a hung/dead producer hits it.
    prefetch_timeout_s: float = 60.0


class DriverLog:
    """Run log with registry-backed storage (duck-type-compatible with
    train.trainer.TrainerLog, which is an alias of this class).

    The public fields are the SAME plain lists PR-2 consumers have
    always indexed — but they are views of Series metrics living in a
    ``MetricsRegistry``, so a metrics-enabled run exports losses, step
    times, straggler and plan-swap events through the JSONL sink with no
    second bookkeeping path. With no registry supplied the log owns a
    private (disabled) one and behaves exactly like the old dataclass.
    """

    def __init__(self, registry: MetricsRegistry | None = None):
        self.registry = registry if registry is not None \
            else MetricsRegistry(enabled=False)
        self.losses = self.registry.series("train/loss").data
        self.step_times = self.registry.series("train/step_time_s").data
        # (step, dt, rolling median) triples
        self.straggler_events = \
            self.registry.series("driver/straggler_events").data
        # (step, plan signature) pairs
        self.plan_swaps = self.registry.series("driver/plan_swaps").data

    @property
    def restarts(self) -> int:
        return self.registry.counter("driver/restarts").value

    @restarts.setter
    def restarts(self, v: int) -> None:
        self.registry.counter("driver/restarts").value = int(v)


def record_step(log, step: int, dt: float, loss: float,
                straggler_factor: float) -> None:
    """Append one step's (loss, wall time) to the log and run the
    straggler watchdog — the ONE logging policy shared by the synchronous
    Trainer.run loop and the async driver, so the two can never drift.

    The watchdog statistic is the rolling MEDIAN of the last
    ``STRAGGLER_WINDOW`` step times (the documented summary of
    ``log.step_times``); a step slower than ``straggler_factor`` times
    that median records a ``(step, dt, median)`` event, bumps the
    ``driver/stragglers`` counter, and the current median is exported as
    the ``driver/straggler_median_s`` gauge."""
    log.losses.append(loss)
    log.step_times.append(dt)
    reg = getattr(log, "registry", None)
    if len(log.step_times) >= STRAGGLER_WARMUP:
        med = median(log.step_times[-STRAGGLER_WINDOW:])
        if reg is not None:
            reg.gauge("driver/straggler_median_s").set(med)
        if dt > straggler_factor * med:
            log.straggler_events.append((step, dt, med))
            if reg is not None:
                reg.counter("driver/stragglers").inc()
                reg.event("driver/straggler", step=step, dt_s=dt,
                          median_s=med, factor=straggler_factor)


class _Prefetcher:
    """Background thread producing HOST batches ahead of dispatch (device
    transfer stays on the main thread). Restartable after a failure."""

    def __init__(self, batch_fn: Callable[[int], Any], prefetch_units: int,
                 steps_per_unit: int):
        self._batch_fn = batch_fn
        self._k = steps_per_unit
        self._cap = max(1, prefetch_units) * steps_per_unit
        self._q: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    def start(self, start_step: int, num_steps: int):
        self.stop()
        self._stop = threading.Event()
        self._q = queue.Queue(maxsize=self._cap)
        stop, q = self._stop, self._q

        def work():
            for s in range(start_step, num_steps):
                if stop.is_set():
                    return
                try:
                    item = (s, self._batch_fn(s))
                except BaseException as e:  # poison-pill: surface in take()
                    item = (None, e)
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if item[0] is None:
                    return

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()

    def take(self, step: int, timeout: float = 60.0):
        """Bounded get: the old unbounded ``q.get()`` hung the dispatch
        loop forever when the producer thread died without enqueueing its
        poison pill (or never produced at all). Poll with a short get so
        thread death is noticed within ~0.5s, and bound total waiting by
        ``timeout`` for a live-but-stalled producer. Both paths surface
        as :class:`PrefetchStalled` — classified 'stall' by the recovery
        supervisor, with the producer's own exception attached as the
        cause when one was captured."""
        assert self._q is not None, "prefetcher not started"
        deadline = time.perf_counter() + timeout
        while True:
            try:
                s, batch = self._q.get(timeout=0.5)
                break
            except queue.Empty:
                alive = self._thread is not None and self._thread.is_alive()
                if not alive and self._q.empty():
                    raise PrefetchStalled(
                        f"prefetch thread died before producing step {step}")
                if time.perf_counter() >= deadline:
                    raise PrefetchStalled(
                        f"no batch for step {step} within {timeout:.1f}s "
                        "(data pipeline stalled)")
        if s is None:  # producer died — re-raise on the driver thread
            raise PrefetchStalled(
                f"prefetch batch_fn failed at step {step}: {batch!r}",
                cause=batch) from batch
        assert s == step, (s, step)
        return batch

    def stop(self):
        if self._thread is not None:
            self._stop.set()
            try:  # drain so the producer can observe the stop flag
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=5)
            self._thread = None


def run_pipelined(
    step_fn: Callable,
    state,
    *,
    start_step: int,
    num_steps: int,
    batch_fn: Callable[[int], Any],
    key_fn: Callable[[int], jax.Array],
    cfg: DriverConfig = DriverConfig(),
    log=None,
    straggler_factor: float = 3.0,
    ckpt_every: Optional[int] = None,
    ckpt_fn: Optional[Callable[[Any], None]] = None,
    restore_fn: Optional[Callable[[], Any]] = None,
    adapt=None,
    obs=None,
    health=None,
    recovery=None,
    injector=None,
):
    """Drive ``step_fn`` from ``start_step`` to ``num_steps`` (absolute).

    step_fn: jitted pipelined step ``(state, batch, key)`` when
    ``cfg.steps_per_unit == 1``, else the scanned superstep taking
    stacked ``(K, ...)`` batches and ``(K, 2)`` keys. A trailing unit
    shorter than K is dispatched with the smaller leading axis (one
    extra compile).
    batch_fn: step -> HOST batch dict (numpy); called from the prefetch
    thread, so it must be thread-compatible (the synthetic pipeline is).
    adapt: an ``runtime.adapt.AdaptiveRuntime`` (duck-typed: ``observe``
    + ``maybe_swap``). Retired units feed it telemetry; when it accepts a
    replan the window is DRAINED (every in-flight unit retired) and the
    compiled step function is swapped at that barrier — TrainState rides
    across unchanged (replans are layout-invariant, DESIGN.md §7), and
    the swap is recorded in ``log.plan_swaps``.
    obs: a ``repro.obs.Observability`` handle (None = session default,
    which defaults to OFF). Host spans + structured events only — the
    retire below stays the ONLY sync point either way.
    health: a ``repro.obs.health.HealthMonitor`` — evaluated at drain
    barriers and at end of run (host-side registry reads, no sync);
    verdicts land as ``health/*`` events, and when ``adapt`` exposes an
    ``advise`` hook the critical findings are handed to it as the
    drain-barrier advisory (DESIGN.md §10.5).
    The flight recorder (``obs.recorder``, when attached) notes every
    retired unit and dumps ``blackbox.json`` on watchdog fire and on any
    exception — including ones the restore path survives.
    recovery: a ``runtime.faults.RecoveryConfig`` (or a prebuilt
    ``RetrySupervisor``) turning the bare restore-on-failure into the
    bounded retry/backoff policy of DESIGN.md §12.3: each failure is
    classified by fault class, charged against that class's retry
    budget, delayed by jittered exponential backoff, then restored;
    an exhausted budget escalates to ``RetryBudgetExhausted`` AFTER the
    blackbox dump (clean abort). ``None`` keeps the legacy unbounded
    restore. Independently, when the step function was built with
    ``guard=True`` the retire path reads ``metrics["nonfinite"]``: each
    tripped (skipped) step emits a critical ``health/nonfinite`` event,
    and ``max_consecutive_nonfinite`` consecutive trips raise
    ``NonFiniteEscalation`` into the same restore path (rewind to the
    last-good checkpoint; the replayed data is clean by the injector's
    one-shot contract, so recovery is bit-reproducible).
    injector: a ``runtime.faults.FaultInjector`` (chaos harness). The
    driver wraps ``batch_fn`` with its stall/nonfinite hooks — so the
    step function MUST then be built with ``inject=True`` — and fires
    its collective/sigterm hook before each dispatch and its straggler
    hook inside each retire.
    Returns (final state, log).
    """
    if cfg.depth < 1 or cfg.prefetch < 1 or cfg.steps_per_unit < 1:
        raise ValueError(f"DriverConfig fields must be >= 1: {cfg}")
    obs = _resolve_obs(obs)
    rec = getattr(obs, "recorder", None)
    if log is None:
        log = DriverLog(registry=obs.metrics if obs.metrics_on else None)
    reg = obs.metrics if obs.metrics_on else None
    supervisor = None
    if recovery is not None:
        supervisor = (recovery if isinstance(recovery, RetrySupervisor)
                      else RetrySupervisor(recovery, registry=reg))
    rcfg = supervisor.cfg if supervisor is not None else RecoveryConfig()
    if injector is not None:
        injector.bind(registry=reg)
        batch_fn = injector.wrap_batch_fn(batch_fn)
    k_unit = cfg.steps_per_unit
    prefetcher = _Prefetcher(batch_fn, cfg.prefetch, k_unit)
    prefetcher.start(start_step, num_steps)
    window: deque = deque()  # (first_step, n_steps, metrics)
    step = start_step
    last_retire_t = time.perf_counter()
    consec_nonfinite = 0  # consecutive guard-tripped steps (§12.2)

    def retire_one():
        nonlocal last_retire_t, consec_nonfinite
        s0, k, metrics = window.popleft()
        with obs.span("driver/retire", step=s0, k=k):
            jax.block_until_ready(metrics["loss"])      # the ONLY sync point
            if injector is not None:
                # straggler hook: the delay lands inside THIS retire
                # interval, so the watchdog sees it as a slow step
                med0 = (median(log.step_times[-STRAGGLER_WINDOW:])
                        if len(log.step_times) >= STRAGGLER_WARMUP else 0.0)
                injector.after_retire(s0, k, med0)
        now = time.perf_counter()
        dt_unit = now - last_retire_t
        dt = dt_unit / k
        last_retire_t = now
        losses = np.atleast_1d(np.asarray(metrics["loss"]))
        n_stragglers = len(log.straggler_events)
        for i in range(k):
            record_step(log, s0 + i, dt,
                        float(losses[i] if k > 1 else losses[0]),
                        straggler_factor)
        if "nonfinite" in metrics:
            # guarded step (§12.2): each tripped step was a state no-op
            # on device; here it becomes a critical health event, and N
            # consecutive trips escalate to a rewind — skip-recovery is
            # not converging, so replay from the last-good checkpoint.
            trips = np.atleast_1d(np.asarray(metrics["nonfinite"]))
            for i in range(k):
                if float(trips[i] if k > 1 else trips[0]) > 0.5:
                    consec_nonfinite += 1
                    if reg is not None:
                        reg.counter("guard/nonfinite_trips").inc()
                    obs.event("health/nonfinite", severity="critical",
                              subject="grads", step=s0 + i,
                              consecutive=consec_nonfinite,
                              message="non-finite grads: apply skipped, "
                                      "EF/opt state preserved")
                    if rec is not None:
                        rec.note("guard/nonfinite", step=s0 + i,
                                 consecutive=consec_nonfinite)
                    if consec_nonfinite >= rcfg.max_consecutive_nonfinite:
                        raise NonFiniteEscalation(
                            f"{consec_nonfinite} consecutive non-finite "
                            f"steps ending at step {s0 + i}")
                else:
                    consec_nonfinite = 0
        if obs.metrics_on:
            obs.metrics.histogram("driver/retire_wall_s").observe(dt_unit)
        if rec is not None:
            rec.note("driver/retire", step=s0, k=k, dt_unit_s=dt_unit,
                     loss=float(losses[-1] if k > 1 else losses[0]))
            if len(log.straggler_events) > n_stragglers:
                rec._safe_dump("watchdog")
        if adapt is not None:
            adapt.observe(s0, k, metrics)

    def drain():
        if window:
            with obs.span("driver/drain", inflight=len(window)):
                while window:
                    retire_one()
        health_check()

    def health_check():
        """Drain-barrier health evaluation: windowed rules over whatever
        the registry holds, critical findings handed to the adaptive
        controller as its urgency advisory. Pure host-side reads."""
        if health is None:
            return
        events = health.evaluate()
        if events and adapt is not None and hasattr(adapt, "advise"):
            adapt.advise(events)

    def check_swap():
        """Install a controller-accepted replan (DESIGN.md §7). Called
        wherever retires may have fed the controller — after dispatches,
        after checkpoint drains, and on the tail drain — so the active
        plan recorded in checkpoint meta is always one that has actually
        been installed (and logged), never a pending acceptance."""
        nonlocal step_fn
        if adapt is None:
            return
        swap = adapt.maybe_swap()
        if swap is None:
            return
        # Plan-swap barrier: drain every in-flight unit, then install
        # the re-planned compiled step. State needs no migration —
        # replans are layout-invariant.
        drain()
        step_fn, new_plan = swap
        if hasattr(log, "plan_swaps"):
            log.plan_swaps.append((step, new_plan.signature()))
        obs.event("driver/plan_swap", step=step,
                  signature=new_plan.signature(),
                  version=getattr(new_plan, "version", None))

    def dispatch(state, step):
        k = min(k_unit, num_steps - step)
        if injector is not None:
            # collective-raise / SIGTERM hook: BEFORE the jitted call, so
            # the donated state is never half-consumed and a restore (or
            # the signal handler's blackbox) sees a consistent world
            injector.before_dispatch(step, k)
        with obs.span("driver/dispatch", step=step, k=k):
            take = lambda s: prefetcher.take(s, cfg.prefetch_timeout_s)
            if k_unit == 1:
                batch = jax.tree.map(jnp.asarray, take(step))
                key = key_fn(step)
            else:
                host = [take(step + i) for i in range(k)]
                batch = jax.tree.map(
                    lambda *xs: jnp.asarray(np.stack(xs)), *host)
                key = jnp.stack([key_fn(step + i) for i in range(k)])
            new_state, metrics = step_fn(state, batch, key)
        window.append((step, k, metrics))
        return new_state, step + k

    try:
        # the final drain runs under the same restore protection as the
        # loop body: a fault surfacing in the last in-flight units is
        # survived exactly like one mid-run
        while step < num_steps or window:
            try:
                if step >= num_steps:
                    retire_one()
                    check_swap()  # keep meta/log consistent on the tail
                    continue
                prev = step
                state, step = dispatch(state, step)
                while len(window) >= cfg.depth:  # at most `depth` in flight
                    retire_one()
                check_swap()
                if (ckpt_every and ckpt_fn is not None and step < num_steps
                        and step // ckpt_every > prev // ckpt_every):
                    # a unit crossed a checkpoint boundary — drain the
                    # window so the save reads a fully retired state
                    # (the drain's retires may accept a replan: install
                    # it before the save records the active plan)
                    drain()
                    check_swap()
                    with obs.span("driver/checkpoint", step=step):
                        ckpt_fn(state)
            except Exception as e:
                if rec is not None:
                    # blackbox BEFORE restore or re-raise: the ring still
                    # holds the pre-failure steps a restart would erase
                    if (isinstance(e, PrefetchStalled)
                            and e.cause is not None):
                        rec.note("driver/prefetch_error",
                                 error=type(e.cause).__name__,
                                 message=str(e.cause))
                    rec._safe_dump(f"exception:{type(e).__name__}")
                if restore_fn is None:
                    raise
                if supervisor is not None:
                    # classify + charge the class budget; raises
                    # RetryBudgetExhausted (clean abort, blackbox above)
                    # when the class is spent, else returns the jittered
                    # backoff delay to wait out before the restore
                    time.sleep(supervisor.on_failure(e, step))
                window.clear()
                consec_nonfinite = 0
                log.restarts += 1
                obs.event("driver/restart", step=step,
                          error=type(e).__name__)
                if injector is not None:
                    # poison produced for never-dispatched steps dies
                    # with the prefetch queue — refund it so the replay
                    # injects it for real (`step` is still the frontier)
                    injector.refund_undispatched(step)
                state = restore_fn()
                step = int(state.step)
                prefetcher.start(step, num_steps)
                last_retire_t = time.perf_counter()
        health_check()  # end-of-run verdicts over the full registry
    finally:
        prefetcher.stop()
    return state, log
