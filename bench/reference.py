"""Plain reference of one data-parallel training step with sparse sync:
what every model shares. It knows no model; each configuration file names
its model's module under ``"reference"``, ``bench/archs/<name>.py``, found
by :func:`arch_module`.

Written from the published descriptions, in straightforward ``jax.numpy``,
and independent of the program under test (it imports nothing of it):

* the numerics the models' modules compute with: contractions
  (:func:`ein`), stored activations (:func:`act`), :func:`rmsnorm`, and
  the weights' draws (:func:`normal`);
* the sync of SparCML's Quantized TopK SGD (paper Alg. 2): per rank, the
  error-fed gradient keeps its ``k`` largest entries of every
  ``bucket``-wide run of its layout; the kept entries are summed over the
  ranks and averaged; the result is QSGD-quantised (``bits`` bits, L2
  scale, ``qsgd_bucket``-wide runs, stochastic rounding from its own
  draws); then clipping by global norm and AdamW with the warm-up /
  cosine schedule, parameters stored in their configured dtype.

A model's module provides:

* ``Arch`` and ``arch(model) -> Arch``: the sizes it needs, from the
  configuration file's ``model`` block (a frozen dataclass: it is a
  static argument of the jitted gradient);
* ``init_params(seed, arch)``: weights from the seed as the program's
  initialiser makes them (same key splits and scales), so program and
  reference start from the same numbers without one taking them from the
  other;
* ``seq_loss(params, tokens, arch, prec)``: the mean next-token loss of
  one sequence, its contractions through :func:`ein` and its stored
  activations through :func:`act`;
* ``flops_per_token(model, seq_len) -> {term: FLOPs, ..., "total"}``: the
  model FLOPs of one training token that ``mfu`` counts, its terms in its
  docstring;
* optionally ``model_axis(path, ndim)``, where its leaves' bucket layout
  differs from :func:`model_axis`;
* ``TINY`` and ``FLOPS_CASES``: a tiny cell and hand counts of
  ``flops_per_token`` for the benchmark's own tests.

Precision: ``prec="f32"`` computes every contraction in float32 at
``Precision.HIGHEST``; ``prec="fp8"`` (the control) rounds where the
program rounds to its bf16: both operands and the result of every
contraction, the convolution's output and the residual stream that each
layer hands on go to float8 e4m3, their gradients to e5m2, each with a
per-tensor scale, as float8 training does (e4m3 in the IEEE layout, so
its largest value is 240 and not e4m3fn's 448: the same three mantissa
bits, one binade less range).

Memory: gradients are taken one sequence at a time, each layer under
``jax.checkpoint``; the residuals and optimizer state live on the host
between steps and visit the device one leaf at a time.
"""
from __future__ import annotations

import functools
import importlib.util
import math
import sys
from dataclasses import dataclass
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
ARCHS = Path(__file__).resolve().parent / "archs"


def arch_module(name: str):
    """The model's reference module ``<ARCHS>/<name>.py``, the name a
    configuration file gives under ``"reference"``; loaded once, so that
    its functions stay the same static arguments of the jitted gradient."""
    return _load(ARCHS / f"{name}.py")


@functools.cache
def _load(path: Path):
    if not path.is_file():
        raise FileNotFoundError(f"no reference module {path}: the "
                                f"configuration's \"reference\" names "
                                f"{path.stem!r}")
    name = f"bench_arch:{path}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod        # a dataclass looks its module up there
    spec.loader.exec_module(mod)
    return mod


@dataclass(frozen=True)
class Opt:
    """Sync and optimizer settings of the configuration."""
    k: int = 4
    bucket: int = 512
    qsgd_bits: int | None = 4
    qsgd_bucket: int = 1024
    peak_lr: float = 3e-4
    warmup: int = 200
    total_steps: int = 20000
    final_frac: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0

    def lr(self, step: int) -> float:
        warm = min(1.0, step / max(1, self.warmup))
        t = min(max((step - self.warmup)
                    / max(1, self.total_steps - self.warmup), 0.0), 1.0)
        frac = self.final_frac + (1 - self.final_frac) * 0.5 * (
            1 + math.cos(math.pi * t))
        return self.peak_lr * warm * frac


def normal(key, shape, scale, dtype):
    """N(0, scale^2) drawn in float32, stored in ``dtype``."""
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ------------------------------------------------------------ contractions

def _round(x, exponent_bits: int, mantissa_bits: int):
    """Round to a float format of ``exponent_bits`` and ``mantissa_bits``
    (IEEE layout, to nearest even), with a per-tensor scale that puts
    the largest entry at the format's largest finite value.

    ``lax.reduce_precision`` and not a round trip through a float8 dtype:
    on the TPU a float32 -> float8 -> float32 round trip does not come
    back rounded (every entry differs from ml_dtypes' rounding), while
    ``reduce_precision`` rounds there as on the CPU."""
    bias = 2 ** (exponent_bits - 1) - 1
    top = (2 - 2.0 ** -mantissa_bits) * 2.0 ** bias
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, top / amax, 1.0)
    return jax.lax.reduce_precision(x * s, exponent_bits=exponent_bits,
                                    mantissa_bits=mantissa_bits) / s


@jax.custom_vjp
def _fp8(x):
    """Float8 training's rounding: values to e4m3 on the way forward,
    their cotangents to e5m2 on the way back, each with a per-tensor
    scale."""
    return _round(x, 4, 3)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_round(g, 5, 2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def act(prec: str, x):
    """An activation where the program stores it in its compute dtype: as
    is, or (the control) rounded to float8."""
    return _fp8(x) if prec == "fp8" else x


def ein(prec: str, eq: str, x, y):
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    if prec == "fp8":
        x, y = _fp8(x), _fp8(y)
    return act(prec, jnp.einsum(eq, x, y, precision=HI))


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


# ------------------------------------------------------------ gradients

@partial(jax.jit, static_argnames=("seq_loss", "a", "prec"),
         donate_argnums=(2,))
def _seq_grad(params, tokens, acc, seq_loss, a, prec: str):
    p32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    loss, g = jax.value_and_grad(seq_loss)(p32, tokens, a, prec)
    return loss, jax.tree.map(jnp.add, acc, g)


def rank_grads(params, rows, seq_loss, a, prec: str, device=None):
    """Losses and summed f32 gradient of the model's ``seq_loss`` over the
    sequences ``rows`` (R, S) of one rank, one sequence at a time,
    dispatched without waiting (so ranks on different devices run side by
    side)."""
    params = jax.device_put(params, device)
    acc = jax.device_put(
        jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        device)
    losses = []
    for r in rows:
        loss, acc = _seq_grad(params, jax.device_put(jnp.asarray(r), device),
                              acc, seq_loss, a, prec)
        losses.append(loss)
    return losses, acc


# ------------------------------------------------------------ sync

def model_axis(path: str, ndim: int) -> int | None:
    """The axis the configuration shards over tensor-parallel chips, which
    sets the rows of the bucket layout: output features of the input
    projections, input features of the output projections, the feature
    axis of the embedding tables; None (one flat row) for the rest."""
    name = path.rsplit("'", 2)[-2] if "'" in path else path
    if ndim <= 1:
        return None
    if name in ("embed", "unembed", "in_proj", "wq", "wk", "wv", "wi", "wg"):
        return ndim - 1
    if name in ("wo", "out_proj"):
        return ndim - 2
    return None


def to_rows(x, ax: int | None, width: int):
    """(rows, cols) layout whose rows are split into ``width``-wide runs;
    cols padded with zeros to a multiple of ``width``."""
    if ax is None:
        x2 = x.reshape(1, -1)
    else:
        x2 = jnp.moveaxis(x, ax, 0).reshape(x.shape[ax], -1)
    pad = -x2.shape[1] % width
    return jnp.pad(x2, ((0, 0), (0, pad))) if pad else x2


def from_rows(x2, shape, ax: int | None):
    n = math.prod(shape)
    if ax is None:
        return x2.reshape(-1)[:n].reshape(shape)
    moved = (shape[ax],) + tuple(s for i, s in enumerate(shape) if i != ax)
    rest = n // shape[ax]
    return jnp.moveaxis(x2[:, :rest].reshape(moved), 0, ax)


@partial(jax.jit, static_argnames=("ax", "k", "bucket"))
def topk_ef(g, res, scale, ax, k: int, bucket: int):
    """Error-fed top-k of every ``bucket``-wide run of ``scale * g`` in the
    rows layout: returns (kept, new residual), both (rows, cols)."""
    acc = to_rows(g * scale, ax, bucket) + res
    rows, cols = acc.shape
    xb = acc.reshape(rows, cols // bucket, bucket)
    _, idx = jax.lax.top_k(jnp.abs(xb), k)
    keep = jnp.zeros(xb.shape, bool)
    keep = jnp.put_along_axis(keep, idx, True, axis=-1, inplace=False)
    kept = jnp.where(keep, xb, 0.0).reshape(rows, cols)
    return kept, jnp.where(keep, 0.0, xb).reshape(rows, cols)


@partial(jax.jit, static_argnames=("bits", "bucket"))
def qsgd(x, key, bits: int, bucket: int):
    """QSGD of every ``bucket``-wide run of (rows, cols): scale = run's L2
    norm, |x|/scale * s rounded stochastically to s = 2^(bits-1)-1
    levels, sign kept."""
    rows, cols = x.shape
    pad = -cols % bucket
    xp = jnp.pad(x, ((0, 0), (0, pad))).reshape(rows, -1, bucket)
    s = 2 ** (bits - 1) - 1
    scale = jnp.sqrt(jnp.sum(xp * xp, axis=-1, keepdims=True))
    safe = jnp.where(scale > 0, scale, 1.0)
    u = jax.random.uniform(key, xp.shape, jnp.float32)
    level = jnp.clip(jnp.floor(jnp.abs(xp) / safe * s + u), 0, s)
    q = jnp.sign(xp) * level / s * scale
    return q.reshape(rows, -1)[:, :cols]


@partial(jax.jit, static_argnames=("dtype",))
def adamw(p, g, m, v, lr, t, b1, b2, eps, wd, dtype):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    c1 = 1 - b1 ** t
    c2 = 1 - b2 ** t
    step = (m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p.astype(jnp.float32)
    return (p.astype(jnp.float32) - lr * step).astype(dtype), m, v


def flat(tree) -> dict:
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


class Trainer:
    """The reference's training state for ``ranks`` data-parallel ranks of
    the model ``mod`` (its module, :func:`arch_module`) at sizes ``a``:
    parameters (configured dtype), per-rank error-feedback residuals and
    AdamW moments (float32), all on the host between steps."""

    def __init__(self, seed: int, mod, a, o: Opt, ranks: int, prec: str):
        self.mod, self.a, self.o, self.ranks, self.prec = mod, a, o, ranks, prec
        self.seed = seed
        self.axis = getattr(mod, "model_axis", model_axis)
        self.params = {k: np.asarray(v) for k, v in
                       flat(jax.jit(mod.init_params, static_argnums=(0, 1))(
                           seed, a)).items()}
        self.tree = jax.tree.structure(
            jax.eval_shape(lambda: mod.init_params(seed, a)))
        self.res = {k: [None] * ranks for k in self.params}
        self.m = {k: None for k in self.params}
        self.v = {k: None for k in self.params}
        self.step = 0

    def unflat(self, d: dict):
        return jax.tree.unflatten(self.tree, [d[k] for k in self.params])

    def train_step(self, rows_by_rank, devices, exchange: bool = True):
        """One step; ``rows_by_rank[r]`` are rank r's sequences. Returns
        (mean loss, synced-and-clipped gradient by leaf path, global norm
        of the synced gradient before clipping); ``self.ef_norm`` is left
        holding the global norm of the error-feedback residuals after the
        step, over all ranks."""
        a, o = self.a, self.o
        params = self.unflat(self.params)
        futs = [rank_grads(params, rows, self.mod.seq_loss, a, self.prec,
                           devices[r % len(devices)])
                for r, rows in enumerate(rows_by_rank)]
        n = sum(len(rows) for rows in rows_by_rank)
        loss = sum(float(x) for ls, _ in futs for x in ls) / n
        grads = [flat(g) for _, g in futs]
        per_rank = [len(rows) for rows in rows_by_rank]
        dev0 = devices[0]
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed ^ 0x5EED),
                                 self.step)
        synced, sq, ef_sq = {}, 0.0, 0.0
        for i, name in enumerate(self.params):
            shape = self.params[name].shape
            ax = self.axis(name, len(shape))
            kept_sum = None
            for r in range(self.ranks):
                g = grads[r][name]
                dev = list(g.devices())[0]
                res = self.res[name][r]
                if res is None:
                    res = np.zeros(jax.eval_shape(
                        lambda x: to_rows(x, ax, o.bucket), g).shape,
                        np.float32)
                kept, res = topk_ef(g, jax.device_put(res, dev),
                                    1.0 / per_rank[r], ax, o.k, o.bucket)
                ef_sq += float(jnp.sum(jnp.square(res)))
                self.res[name][r] = np.asarray(res)
                kept = jax.device_put(kept, dev0)
                if not exchange:
                    kept_sum = kept * self.ranks   # rank 0's own, unsummed
                    break
                kept_sum = kept if kept_sum is None else kept_sum + kept
            mean = kept_sum / self.ranks
            if o.qsgd_bits is not None:
                mean = qsgd(mean, jax.random.fold_in(key, i), o.qsgd_bits,
                            o.qsgd_bucket)
            synced[name] = from_rows(mean, shape, ax)
            sq += float(jnp.sum(jnp.square(synced[name])))
        del grads, futs
        self.ef_norm = math.sqrt(ef_sq)
        norm = math.sqrt(sq)
        factor = min(1.0, o.grad_clip / (norm + 1e-9))
        lr = o.lr(self.step)
        t = float(self.step + 1)
        for name, g in synced.items():
            g = g * factor
            synced[name] = g
            p = self.params[name]
            m = self.m[name] if self.m[name] is not None else np.zeros(
                p.shape, np.float32)
            v = self.v[name] if self.v[name] is not None else np.zeros(
                p.shape, np.float32)
            p2, m2, v2 = adamw(jax.device_put(p, dev0), g,
                               jax.device_put(m, dev0), jax.device_put(v, dev0),
                               lr, t, o.beta1, o.beta2, o.eps, o.weight_decay,
                               p.dtype)
            self.params[name] = np.asarray(p2)
            self.m[name], self.v[name] = np.asarray(m2), np.asarray(v2)
        self.step += 1
        return loss, synced, norm
