"""Bring-up smoke run on the TPU: mamba2-370m training with sparse sync.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # one four-chip host

One chip (the default):
  * kernels: the four Pallas kernels compiled for the chip
    (``interpret=False``) at the bucket counts of the mamba2-370m sync
    plan, each checked against its jnp reference and for a
    ``tpu_custom_call`` in its compiled HLO;
  * training: mamba2-370m at its published config (48 layers, d_model
    1024, ssm_state 128, vocab 50,280, bf16) with the paper's sync setting
    (``configs.get_train_config``: top-k 4 of every 512, DSAR with a 4-bit
    QSGD phase, ZeRO-1) at seq 4096 — a few synchronous ``Trainer.run``
    steps, then pipelined steps (``Trainer.run_pipelined``, staleness 1,
    one step per dispatch: a scanned or unrolled superstep of two steps
    needs 19.5 GB of the chip's 15.75). Every loss must be finite, with
    no restart, no guarded-step trip and no compile once the first step
    of each loop has retired.

Four chips (``--four-chips``, and nothing else): the same model on a
``(4, 1)`` ("data", "model") mesh at global batch 32, sparse sync against
``mode="dense"`` from the same initialisation and batches. Step-0 losses
must agree, later losses stay within a band, the compiled sparse step
must hold an ``all-to-all``, and parameters, EF residuals and ZeRO-1
moment chunks must live on all four devices.

The global batch of ``train_4k`` (256) is cut to 8 sequences per chip.
There is no checkpoint directory and no recovery policy, so any failure
raises. Settings, step times and peak device memory go to earlier lines;
the last line of stdout is one JSON object naming the device. Off the TPU
the script exits 2 before doing anything. One process owns the chips:
nothing here starts another.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "mamba2_370m"
SEQ_PER_CHIP = 8        # global batch per chip (train_4k's 256 is cut)
MICROBATCHES = 8
SYNC_STEPS = 4          # Trainer.run steps (the first one compiles)
SUPERSTEP = 1           # steps per pipelined dispatch (K=2 does not fit)
SUPERSTEPS = 4          # pipelined dispatches (the first one compiles)
FOUR_CHIP_STEPS = 4
STEP0_RTOL = 1e-3       # sparse vs dense loss at step 0 (same params/batch)
LOSS_BAND_RTOL = 1e-2   # sparse vs dense loss at later steps


class SmokeFailure(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


class CompileLog:
    """Every backend compile (persistent-cache hits included), with the
    number of training steps the log had recorded when it happened."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.entries: list[tuple[str, int]] = []
        self.step_log = None
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            n = len(self.step_log.step_times) if self.step_log else 0
            self.entries.append((str(kw.get("fun_name", "?")), n))

    def mark(self) -> int:
        return len(self.entries)

    def late_compiles(self, since: int, steps_before: int) -> list[str]:
        """Programs compiled once the first step of a loop that started
        with ``steps_before`` recorded steps had retired."""
        return [n for n, s in self.entries[since:] if s > steps_before]


# ---------------------------------------------------------------- kernels

def plan_bucket_counts(plan, sync) -> list[tuple[int, int]]:
    """(top-k buckets, QSGD buckets) of the one-chip plan's flat group and
    of its first model-row group: the per-call row counts the four
    kernels see."""
    out = []
    for g in plan.groups[:2]:
        n = g.buckets[0].rows * g.buckets[0].cols
        out.append((n // sync.bucket_size, n // sync.qsgd_bucket))
    return out


def kernel_phase(counts, sync) -> None:
    from repro.kernels.bucket_scatter.kernel import bucket_scatter_pallas
    from repro.kernels.bucket_scatter.ref import bucket_scatter_ref
    from repro.kernels.bucket_topk.kernel import bucket_topk_pallas
    from repro.kernels.bucket_topk.ref import bucket_topk_ref
    from repro.kernels.qsgd_pack.kernel import qsgd_pack_pallas
    from repro.kernels.qsgd_pack.ref import qsgd_pack_ref
    from repro.kernels.qsgd_unpack.kernel import qsgd_unpack_pallas
    from repro.kernels.qsgd_unpack.ref import qsgd_unpack_ref

    b, k, bits, bq = (sync.bucket_size, sync.k_per_bucket, sync.qsgd_bits,
                      sync.qsgd_bucket)
    key = jax.random.PRNGKey(0)

    def compiled(name, fn, *args):
        c = jax.jit(fn).lower(*args).compile()
        check("tpu_custom_call" in c.as_text(),
              f"{name}: no tpu_custom_call in the compiled HLO")
        return c(*args)

    def codes(packed):
        """Biased QSGD codes out of packed words, on the host."""
        p = np.asarray(packed)
        return np.stack([(p >> (j * bits)) & (2**bits - 1)
                         for j in range(32 // bits)], axis=1)

    for nb, nbq in counts:
        x = jax.random.normal(key, (nb, b), jnp.float32)
        val, lidx, res = compiled(
            f"bucket_topk nb={nb}",
            lambda x: bucket_topk_pallas(x, k, interpret=False), x)
        rv, rl, rr = bucket_topk_ref(x, k)
        check(np.array_equal(lidx, rl) and np.array_equal(val, rv)
              and np.array_equal(res, rr), f"bucket_topk nb={nb} != ref")
        dense = compiled(
            f"bucket_scatter nb={nb}",
            lambda l, v: bucket_scatter_pallas(l, v, b, interpret=False),
            rl, rv)
        check(np.array_equal(dense, bucket_scatter_ref(rl, rv, b)),
              f"bucket_scatter nb={nb} != ref")

        xq = jax.random.normal(key, (nbq, bq), jnp.float32)
        rand = jax.random.bits(jax.random.fold_in(key, 1), (nbq, bq),
                               jnp.uint32)
        packed, scale = compiled(
            f"qsgd_pack nbq={nbq}",
            lambda x, r: qsgd_pack_pallas(x, r, bits, sync.qsgd_scale,
                                          interpret=False), xq, rand)
        rp, rs = qsgd_pack_ref(xq, rand, bits, sync.qsgd_scale)
        # The bucket norm is a reduction, so the chip's kernel and XLA may
        # round it differently; a code then moves by at most one level.
        np.testing.assert_allclose(scale, rs, rtol=1e-6)
        dc = np.abs(codes(packed).astype(np.int64) - codes(rp))
        check(dc.max() <= 1 and dc.mean() < 1e-3,
              f"qsgd_pack nbq={nbq}: {np.count_nonzero(dc)} codes != ref")
        xhat = compiled(
            f"qsgd_unpack nbq={nbq}",
            lambda p, s: qsgd_unpack_pallas(p, s, bits, interpret=False),
            rp, rs)
        np.testing.assert_allclose(xhat, qsgd_unpack_ref(rp, rs, bits),
                                   rtol=1e-6, atol=0)
        print(f"kernels: bucket_topk/bucket_scatter nb={nb} (B={b}, k={k}), "
              f"qsgd_pack/unpack nbq={nbq} (Bq={bq}, {bits} bit): "
              f"compiled for the chip, match ref "
              f"(pack codes off by one: {np.count_nonzero(dc)})")


# --------------------------------------------------------------- training

def build(mesh, mode: str = "sparcml"):
    from repro import configs
    from repro.configs._common import default_sync
    from repro.data.pipeline import DataConfig
    from repro.models.model import build_model

    cfg = configs.get_config(ARCH)
    tcfg = configs.get_train_config(ARCH, microbatches=MICROBATCHES)
    if mode != "sparcml":
        tcfg = dataclasses.replace(tcfg, sync=default_sync(mode),
                                   zero1=False)
    shape = configs.SHAPES["train_4k"]
    data = DataConfig(global_batch=SEQ_PER_CHIP * mesh.devices.size,
                      seq_len=shape.seq_len, vocab_size=cfg.vocab_size)
    return build_model(cfg), tcfg, data


def describe(model, tcfg, data, mesh) -> None:
    from repro import configs

    cfg, s = model.cfg, tcfg.sync
    shape = configs.SHAPES["train_4k"]
    print(f"model: {cfg.name} {cfg.num_layers} layers d_model={cfg.d_model} "
          f"ssm_state={cfg.ssm_state} vocab={cfg.vocab_size} "
          f"dtype={jnp.dtype(cfg.dtype).name} "
          f"params={cfg.param_count() / 1e6:.1f}M")
    print(f"sync: mode={s.mode} k={s.k_per_bucket}/{s.bucket_size} "
          f"algorithm={s.algorithm} qsgd_bits={s.qsgd_bits} "
          f"zero1={tcfg.zero1} impl={s.impl}")
    print(f"mesh: {dict(mesh.shape)}; seq_len={data.seq_len} "
          f"global_batch={data.global_batch} microbatches={tcfg.microbatches}")
    print(f"cut: global batch {shape.global_batch} -> {data.global_batch} "
          f"({SEQ_PER_CHIP} sequences per chip); depth and widths as "
          f"published; weights random from seed {tcfg.seed}")


def peak_bytes() -> list[int]:
    return [int(d.memory_stats()["peak_bytes_in_use"]) for d in jax.devices()]


def check_losses(losses, what: str) -> None:
    check(all(math.isfinite(x) for x in losses),
          f"{what}: non-finite loss in {losses}")


def train_phase(model, tcfg, data, mesh, clog: CompileLog) -> dict:
    from repro import obs as obs_mod
    from repro.train.trainer import Trainer

    obs = obs_mod.configure(metrics=True)   # counts guarded-step trips
    trainer = Trainer(model, tcfg, mesh, data, obs=obs)
    log = trainer.log
    clog.step_log = log
    out = {}

    since = clog.mark()
    t0 = time.perf_counter()
    trainer.run(SYNC_STEPS)
    out["sync_wall_s"] = time.perf_counter() - t0
    late = clog.late_compiles(since, 0)
    check(not late, f"Trainer.run compiled after its first step: {late}")
    out["sync_first_step_s"] = log.step_times[0]
    out["sync_median_step_s"] = statistics.median(log.step_times[1:])
    check_losses(log.losses, "Trainer.run")

    n0 = len(log.step_times)
    since = clog.mark()
    t0 = time.perf_counter()
    trainer.run_pipelined(SYNC_STEPS + SUPERSTEP * SUPERSTEPS, staleness=1,
                          superstep=SUPERSTEP)
    out["pipelined_wall_s"] = time.perf_counter() - t0
    late = clog.late_compiles(since, n0)
    check(not late, f"run_pipelined compiled after its first step: {late}")
    pipe = log.step_times[n0 + SUPERSTEP:]
    out["pipelined_median_step_s"] = statistics.median(pipe)
    check_losses(log.losses, "run_pipelined")
    check(len(log.losses) == SYNC_STEPS + SUPERSTEP * SUPERSTEPS,
          f"expected {SYNC_STEPS + SUPERSTEP * SUPERSTEPS} losses, "
          f"got {len(log.losses)}")
    check(log.restarts == 0, f"restarts={log.restarts}")
    trips = log.registry.counter("guard/nonfinite_trips").value
    check(trips == 0, f"{trips} guarded-step trip(s)")
    out["losses"] = list(log.losses)
    out["compiles"] = len(clog.entries)
    return out


def one_chip(clog: CompileLog) -> None:
    from repro.launch.mesh import make_host_mesh
    from repro.train import train_step as ts

    mesh = make_host_mesh(data=1)
    model, tcfg, data = build(mesh)
    describe(model, tcfg, data, mesh)
    _, _, plan = ts.state_shapes(model, tcfg, mesh, return_plan=True)
    counts = plan_bucket_counts(plan, tcfg.sync)
    print(f"plan: {plan.num_buckets} buckets; kernel row counts "
          f"(top-k, QSGD) per bucket: {counts}")

    t0 = time.perf_counter()
    kernel_phase(counts, tcfg.sync)
    print(f"kernels: done in {time.perf_counter() - t0:.1f} s")

    r = train_phase(model, tcfg, data, mesh, clog)
    print(f"train: losses {['%.4f' % x for x in r['losses']]}")
    print(f"train: Trainer.run {SYNC_STEPS} steps in {r['sync_wall_s']:.1f} s, "
          f"first step (compile) {r['sync_first_step_s']:.1f} s, median "
          f"step after warm-up {r['sync_median_step_s'] * 1e3:.1f} ms")
    print(f"train: run_pipelined {SUPERSTEPS} x {SUPERSTEP}-step dispatches "
          f"in {r['pipelined_wall_s']:.1f} s, median step after the first "
          f"dispatch {r['pipelined_median_step_s'] * 1e3:.1f} ms")
    print(f"train: {r['compiles']} backend compiles in the process; "
          f"peak bytes per device {peak_bytes()}")


# ------------------------------------------------------------- four chips

def four_chips() -> None:
    from repro.data.pipeline import synthetic_batch
    from repro.launch.mesh import make_host_mesh
    from repro.train.trainer import Trainer

    check(len(jax.devices()) == 4,
          f"--four-chips needs 4 devices, found {len(jax.devices())}")
    mesh = make_host_mesh()
    check(dict(mesh.shape) == {"data": 4, "model": 1}, f"mesh {mesh.shape}")
    losses = {}
    for mode in ("sparcml", "dense"):
        model, tcfg, data = build(mesh, mode)
        describe(model, tcfg, data, mesh)
        trainer = Trainer(model, tcfg, mesh, data)
        trainer.init_or_resume()
        if mode == "sparcml":
            batch = jax.tree.map(jnp.asarray, synthetic_batch(data, 0))
            key = jax.random.PRNGKey(0)
            t0 = time.perf_counter()
            hlo = trainer.step_fn.lower(trainer.state, batch, key) \
                .compile().as_text()
            print(f"four chips: sparse step compiled in "
                  f"{time.perf_counter() - t0:.1f} s; all-to-all ops: "
                  f"{hlo.count('all-to-all(')}, all-gather ops: "
                  f"{hlo.count('all-gather(')}")
            check("all-to-all" in hlo, "no all-to-all in the sparse step")
            s = trainer.state
            for part, tree in (("params", s.params), ("ef", s.residuals),
                               ("opt", s.opt)):
                sets = {len(x.sharding.device_set)
                        for x in jax.tree.leaves(tree)}
                check(sets == {4}, f"{part}: leaves on {sets} devices")
                print(f"four chips: {part} leaves on 4 devices, "
                      f"{sum(x.nbytes for x in jax.tree.leaves(tree)) / 1e9:.2f}"
                      f" GB in all")
        t0 = time.perf_counter()
        log = trainer.run(FOUR_CHIP_STEPS)
        print(f"four chips: {mode} {FOUR_CHIP_STEPS} steps in "
              f"{time.perf_counter() - t0:.1f} s, median step after warm-up "
              f"{statistics.median(log.step_times[1:]) * 1e3:.1f} ms, "
              f"losses {['%.5f' % x for x in log.losses]}")
        check_losses(log.losses, mode)
        check(log.restarts == 0, f"{mode}: restarts={log.restarts}")
        losses[mode] = list(log.losses)
        peaks = peak_bytes()
        print(f"four chips: peak bytes per device after {mode}: {peaks}")
        check(all(p > 0 for p in peaks), f"a device shows no use: {peaks}")
        del trainer
    sp, de = np.array(losses["sparcml"]), np.array(losses["dense"])
    rel = np.abs(sp - de) / np.abs(de)
    print(f"four chips: |sparse - dense| / dense per step: "
          f"{['%.2e' % x for x in rel]} (step 0 limit {STEP0_RTOL}, "
          f"later {LOSS_BAND_RTOL})")
    check(rel[0] <= STEP0_RTOL, f"step-0 losses differ: {sp[0]} vs {de[0]}")
    check(np.all(rel[1:] <= LOSS_BAND_RTOL),
          f"sparse leaves the loss band: {sp} vs {de}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sparse-vs-dense phase")
    args = ap.parse_args()

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {devices[0].device_kind} x {len(devices)}; "
          f"jax {jax.__version__}; compile cache {enable_compile_cache()}")
    try:
        four_chips() if args.four_chips else one_chip(CompileLog())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
