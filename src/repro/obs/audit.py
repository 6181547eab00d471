"""Cost-model drift auditor: predicted vs measured, per algorithm.

The adaptive controller picks bucket algorithms from
``cost_model.bucket_time`` and plans steps with ``t_step_overlapped``;
nobody checks those numbers against reality. This module closes the loop
(DESIGN.md §10):

  DriftAuditor            joins (algorithm, predicted_s, measured_s)
                          samples and reports per-algorithm residual
                          stats — median measured/predicted ratio, mean
                          relative error, a ``flagged`` bit when the
                          ratio leaves the trust band — i.e. when
                          ``select_algorithm`` is being lied to
  audit_sync_plan         probes each distinct bucket signature of a
                          training SyncPlan with the standalone
                          ``make_sparse_allreduce`` collective and joins
                          against ``bucket_time``
  audit_serve_plan        same join for a ServePlan's activation
                          exchange (``exchange_activation_spmd`` vs the
                          stream/dense cost entries)

Probes run the real executor halves but OUTSIDE the training loop (at
drain barriers or run end), so the audit adds no sync points to the
pipelined hot path. The per-algorithm median ratio doubles as the
calibrator's quality signal: ``utils.calibrate`` records its post-fit
ladder residuals here, and ``net_scale_hint`` says how far the fitted
alpha-beta model sits from what the probes actually measured.
"""
from __future__ import annotations

import time

import numpy as np


class DriftAuditor:
    """Accumulates predicted-vs-measured samples; reports per algorithm.

    ``flag_ratio`` bounds the trust band: an algorithm whose median
    measured/predicted ratio falls outside [1/flag_ratio, flag_ratio]
    is flagged as drifted.
    """

    def __init__(self, flag_ratio: float = 3.0):
        if flag_ratio <= 1.0:
            raise ValueError("flag_ratio must be > 1")
        self.flag_ratio = float(flag_ratio)
        self.samples: list[dict] = []

    def record(self, algorithm: str, name: str, predicted_s: float,
               measured_s: float, **extra) -> None:
        self.samples.append({
            "algorithm": algorithm, "name": name,
            "predicted_s": float(predicted_s),
            "measured_s": float(measured_s), **extra,
        })

    def __len__(self) -> int:
        return len(self.samples)

    # -- joins -------------------------------------------------------------
    def per_algorithm(self) -> dict[str, dict]:
        by: dict[str, list[dict]] = {}
        for s in self.samples:
            by.setdefault(s["algorithm"], []).append(s)
        out = {}
        for alg, rows in sorted(by.items()):
            pred = np.asarray([r["predicted_s"] for r in rows])
            meas = np.asarray([r["measured_s"] for r in rows])
            ok = pred > 0
            ratio = np.where(ok, meas / np.where(ok, pred, 1.0), np.nan)
            med = float(np.nanmedian(ratio)) if ok.any() else float("nan")
            rel = np.abs(meas - pred) / np.where(ok, pred, 1.0)
            out[alg] = {
                "count": int(len(rows)),
                "predicted_total_s": float(pred.sum()),
                "measured_total_s": float(meas.sum()),
                "median_ratio": med,
                "mean_rel_err": float(np.nanmean(np.where(ok, rel, np.nan)))
                if ok.any() else float("nan"),
                "flagged": bool(np.isfinite(med) and not
                                (1.0 / self.flag_ratio <= med
                                 <= self.flag_ratio)),
            }
        return out

    def net_scale_hint(self) -> float | None:
        """Overall median measured/predicted ratio — the single scalar a
        calibrator can fold back into its fitted params (``None`` until
        at least one positive-prediction sample exists)."""
        r = [s["measured_s"] / s["predicted_s"] for s in self.samples
             if s["predicted_s"] > 0]
        return float(np.median(r)) if r else None

    def flagged_algorithms(self) -> list[str]:
        return [a for a, st in self.per_algorithm().items() if st["flagged"]]

    def report(self) -> dict:
        return {
            "kind": "drift_audit",
            "flag_ratio": self.flag_ratio,
            "samples": int(len(self.samples)),
            "net_scale_hint": self.net_scale_hint(),
            "per_algorithm": self.per_algorithm(),
            "flagged": self.flagged_algorithms(),
        }

    def emit(self, registry) -> None:
        """Mirror the per-algorithm join into the metrics registry as
        ``audit/algorithm_residual`` events (one per algorithm)."""
        for alg, st in self.per_algorithm().items():
            registry.event("audit/algorithm_residual", algorithm=alg, **st)
        hint = self.net_scale_hint()
        if hint is not None:
            registry.gauge("audit/net_scale_hint").set(hint)

    def summary(self) -> str:
        stats = self.per_algorithm()
        if not stats:
            return "  (no audit samples)"
        w = max(len(a) for a in stats)
        lines = [f"  {'algorithm':<{w}}  {'n':>3}  {'pred_ms':>9}  "
                 f"{'meas_ms':>9}  {'med_ratio':>9}  flag"]
        for alg, st in stats.items():
            lines.append(
                f"  {alg:<{w}}  {st['count']:>3}  "
                f"{st['predicted_total_s'] * 1e3:>9.3f}  "
                f"{st['measured_total_s'] * 1e3:>9.3f}  "
                f"{st['median_ratio']:>9.3f}  "
                f"{'DRIFT' if st['flagged'] else 'ok'}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Plan probes: time the real collectives, join against the cost model.
# ---------------------------------------------------------------------------

def _time_fn(fn, args, reps: int) -> float:
    """Best-of-reps wall time of a jitted call (one warmup)."""
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def audit_sync_plan(plan, mesh, axis_name: str = "data", *, net=None,
                    reps: int = 3, auditor: DriftAuditor | None = None,
                    registry=None, max_n: int = 1 << 22) -> DriftAuditor:
    """Probe each DISTINCT (algorithm, n, k) bucket signature of a
    training ``SyncPlan`` with the standalone sparse allreduce and record
    predicted (``bucket_time``) vs measured into ``auditor``.

    One probe per signature, not per bucket — same compiled collective,
    same cost entry. Buckets with n > ``max_n`` are skipped (probing them
    would dominate the run being audited)."""
    import jax
    import jax.numpy as jnp

    from repro.core.allreduce import make_sparse_allreduce
    from repro.core.cost_model import DEFAULT_NET, bucket_time

    net = net or DEFAULT_NET
    auditor = auditor if auditor is not None else DriftAuditor()
    p = mesh.shape[axis_name]
    cfg = plan.cfg
    vb = cfg.qsgd_bits if cfg.qsgd_bits is not None else 32
    impl = getattr(cfg, "impl", "auto")

    seen: set[tuple] = set()
    for g in plan.groups:
        for b in g.buckets:
            k = plan.bucket_k(g, b)
            sig = (b.algorithm, b.n, k)
            if sig in seen:
                continue
            seen.add(sig)
            if b.n > max_n:
                if registry is not None:
                    registry.event("audit/bucket_skipped", name=b.name,
                                   n=b.n, reason=f"n > max_n={max_n}")
                continue
            predicted = bucket_time(b.algorithm, p, k, b.n, net, vb)
            try:
                fn = make_sparse_allreduce(
                    mesh, axis_name, n=b.n,
                    k_per_bucket=cfg.k_per_bucket,
                    bucket_size=cfg.bucket_size,
                    algorithm=b.algorithm, impl=impl)
                key = jax.random.PRNGKey(hash(sig) & 0x7FFFFFFF)
                x = jax.random.normal(key, (p, b.n), jnp.float32)
                measured = _time_fn(fn, (x, None), reps)
            except Exception as e:  # pragma: no cover - probe robustness
                if registry is not None:
                    registry.event("audit/bucket_probe_failed", name=b.name,
                                   algorithm=b.algorithm, error=str(e))
                continue
            auditor.record(b.algorithm, b.name, predicted, measured,
                           n=b.n, k=k, p=p, kind="train_bucket")
    if registry is not None:
        auditor.emit(registry)
    return auditor


def audit_serve_plan(plan, mesh, axis_name: str = "model", *, net=None,
                     reps: int = 3, auditor: DriftAuditor | None = None,
                     registry=None) -> DriftAuditor:
    """Probe a ``ServePlan``'s activation exchange: time
    ``exchange_activation_spmd`` on a model-axis-sharded (p, T, d)
    partials stack and join against the stream/dense cost entries."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.comm.executor import exchange_activation_spmd
    from repro.core.cost_model import DEFAULT_NET, bucket_time

    net = net or DEFAULT_NET
    auditor = auditor if auditor is not None else DriftAuditor()
    p = mesh.shape[axis_name]

    for b in plan.buckets:
        predicted = bucket_time(b.algorithm, p, b.d, b.n, net)
        try:
            fn = jax.jit(lambda x, alg=b.algorithm:
                         exchange_activation_spmd(x, alg))
            key = jax.random.PRNGKey(hash((b.name, b.algorithm))
                                     & 0x7FFFFFFF)
            x = jax.random.normal(key, (p, b.tokens, b.d), jnp.float32)
            x = jax.device_put(x, NamedSharding(mesh, P(axis_name)))
            measured = _time_fn(fn, (x,), reps)
        except Exception as e:  # pragma: no cover - probe robustness
            if registry is not None:
                registry.event("audit/bucket_probe_failed", name=b.name,
                               algorithm=b.algorithm, error=str(e))
            continue
        auditor.record(b.algorithm, b.name, predicted, measured,
                       n=b.n, k=b.d, p=p, kind="serve_bucket")
    if registry is not None:
        auditor.emit(registry)
    return auditor
