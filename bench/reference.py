"""Plain reference of one data-parallel training step with sparse sync.

Written from the published descriptions, in straightforward ``jax.numpy``,
and independent of the program under test (it imports nothing of it):

* the model: Mamba-2 (arXiv:2405.21060; SSD as a chunked scan, written
  out), and the Zamba2-style hybrid (arXiv:2411.15242) that follows every
  ``attn_every`` Mamba-2 layers with one shared attention + SwiGLU block;
  next-token cross-entropy over the padded vocabulary;
* the sync of SparCML's Quantized TopK SGD (paper Alg. 2): per rank, the
  error-fed gradient keeps its ``k`` largest entries of every
  ``bucket``-wide run of its layout; the kept entries are summed over the
  ranks and averaged; the result is QSGD-quantised (``bits`` bits, L2
  scale, ``qsgd_bucket``-wide runs, stochastic rounding from its own
  draws); then clipping by global norm and AdamW with the warm-up /
  cosine schedule, parameters stored in their configured dtype.

Weights are made from the seed as the configuration's initialiser makes
them (same key splits and scales), so program and reference start from
the same numbers without one taking them from the other.

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

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class Arch:
    """The sizes the reference needs, read from the configuration file."""
    family: str            # "ssm" | "hybrid"
    num_layers: int
    d_model: int
    vocab_size: int
    ssm_state: int
    ssm_expand: int
    ssm_head_dim: int
    ssm_chunk: int
    conv_width: int
    num_heads: int = 1
    num_kv_heads: int = 1
    head_dim: int = 0
    d_ff: int = 0
    attn_every: int = 0
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    param_dtype: str = "bfloat16"

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab_size // 256) * 256

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)


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


# ------------------------------------------------------------ weights

def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _mamba_layer(key, a: Arch) -> dict:
    d, di, n, h = a.d_model, a.d_inner, a.ssm_state, a.ssm_heads
    ks = jax.random.split(key, 5)
    conv_dim = di + 2 * n
    return {
        "ln": {"scale": jnp.ones((d,), a.pdtype)},
        "mixer": {
            "in_proj": _normal(ks[0], (d, 2 * di + 2 * n + h),
                               1 / math.sqrt(d), a.pdtype),
            "conv_w": _normal(ks[1], (a.conv_width, conv_dim),
                              1 / math.sqrt(a.conv_width), a.pdtype),
            "conv_b": jnp.zeros((conv_dim,), a.pdtype),
            "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
            "D": jnp.ones((h,), jnp.float32),
            "dt_bias": jnp.zeros((h,), jnp.float32),
            "norm": {"scale": jnp.ones((di,), a.pdtype)},
            "out_proj": _normal(ks[2], (di, d),
                                1 / math.sqrt(di * 2 * a.num_layers), a.pdtype),
        },
    }


def _shared_block(key, a: Arch) -> dict:
    d, nh, nkv, hd, ff = (a.d_model, a.num_heads, a.num_kv_heads, a.head_dim,
                          a.d_ff)
    k1, k2 = jax.random.split(key)
    ka = jax.random.split(k1, 4)
    km = jax.random.split(k2, 3)
    return {
        "ln1": {"scale": jnp.ones((d,), a.pdtype)},
        "attn": {
            "wq": _normal(ka[0], (d, nh * hd), 1 / math.sqrt(d), a.pdtype),
            "wk": _normal(ka[1], (d, nkv * hd), 1 / math.sqrt(d), a.pdtype),
            "wv": _normal(ka[2], (d, nkv * hd), 1 / math.sqrt(d), a.pdtype),
            "wo": _normal(ka[3], (nh * hd, d),
                          1 / math.sqrt(nh * hd * 2 * a.num_layers), a.pdtype),
        },
        "ln2": {"scale": jnp.ones((d,), a.pdtype)},
        "mlp": {
            "wi": _normal(km[0], (d, ff), 1 / math.sqrt(d), a.pdtype),
            "wo": _normal(km[1], (ff, d),
                          1 / math.sqrt(ff * 2 * a.num_layers), a.pdtype),
            "wg": _normal(km[2], (d, ff), 1 / math.sqrt(d), a.pdtype),
        },
    }


def init_params(seed: int, a: Arch) -> dict:
    """Weights from the seed, as the configuration's initialiser makes
    them: embedding N(0, 0.02), projections N(0, 1/fan_in), output
    projections scaled by 1/sqrt(2 * layers), norms at one, biases at
    zero, A = -[1..16], D = 1; layers stacked on a leading axis."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 8)
    v, d = a.padded_vocab, a.d_model
    p = {"embed": _normal(ks[0], (v, d), 0.02, a.pdtype),
         "final_norm": {"scale": jnp.ones((d,), a.pdtype)}}
    if not a.tie_embeddings:
        p["unembed"] = _normal(ks[1], (d, v), d ** -0.5, a.pdtype)
    layer = lambda k: _mamba_layer(k, a)
    if a.family == "ssm":
        p["blocks"] = jax.vmap(layer)(jax.random.split(ks[2], a.num_layers))
    elif a.family == "hybrid":
        nsb = a.num_layers // a.attn_every
        p["blocks"] = jax.vmap(
            lambda k: jax.vmap(layer)(jax.random.split(k, a.attn_every)))(
                jax.random.split(ks[2], nsb))
        p["shared_block"] = _shared_block(ks[3], a)
    else:
        raise ValueError(a.family)
    return p


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


# ------------------------------------------------------------ model

def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def ssd(x, dt, a_log, b, c, d_skip, chunk: int, prec: str):
    """y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s x_s + D x_t
    for one sequence, computed chunk by chunk. x: (S,H,P), dt: (S,H),
    b/c: (S,N)."""
    s, h, p = x.shape
    nc = s // chunk
    a = -jnp.exp(a_log)                                   # (H,)
    da = dt * a                                           # (S,H)
    xc = x.reshape(nc, chunk, h, p)
    dtc = dt.reshape(nc, chunk, h)
    dac = da.reshape(nc, chunk, h)
    bc = b.reshape(nc, chunk, -1)
    cc = c.reshape(nc, chunk, -1)
    cum = jnp.cumsum(dac, axis=1)                         # (nc,Q,H)
    # decay from position j to position i inside a chunk (i >= j)
    seg = cum[:, :, None, :] - cum[:, None, :, :]         # (nc,Qi,Qj,H)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[None, :, :, None]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    cb = ein(prec, "cin,cjn->cij", cc, bc)                # (nc,Qi,Qj)
    w = cb[..., None] * decay * dtc[:, None, :, :]        # (nc,Qi,Qj,H)
    y_in = ein(prec, "cijh,cjhp->cihp", w, xc)
    # state at the end of each chunk from its own inputs
    to_end = jnp.exp(cum[:, -1:, :] - cum) * dtc          # (nc,Q,H)
    st = ein(prec, "cjn,cjhp->chpn", bc, xc * to_end[..., None])

    def carry(state, inp):
        st_c, tot = inp
        return state * jnp.exp(tot)[:, None, None] + st_c, state

    _, entering = jax.lax.scan(carry, jnp.zeros((h, p, st.shape[-1])),
                               (st, cum[:, -1, :]))       # (nc,H,P,N)
    y_off = ein(prec, "cin,chpn->cihp", cc, entering) * jnp.exp(cum)[..., None]
    y = (y_in + y_off).reshape(s, h, p)
    return y + d_skip[None, :, None] * x


def mamba_layer(lp, a: Arch, x, prec):
    """One residual Mamba-2 layer on (S, d)."""
    s = x.shape[0]
    di, n, h, hp = a.d_inner, a.ssm_state, a.ssm_heads, a.ssm_head_dim
    m = lp["mixer"]
    u = rmsnorm(x, lp["ln"]["scale"], a.norm_eps)
    zxbcdt = ein(prec, "sd,de->se", u, m["in_proj"])
    z = zxbcdt[:, :di]
    xbc = zxbcdt[:, di:2 * di + 2 * n]
    dt = zxbcdt[:, 2 * di + 2 * n:]
    w = m["conv_w"].astype(jnp.float32)
    width = w.shape[0]
    pad = jnp.pad(xbc, ((width - 1, 0), (0, 0)))
    conv = sum(pad[i:i + s] * w[i] for i in range(width))
    xbc = act(prec, jax.nn.silu(conv + m["conv_b"].astype(jnp.float32)))
    xs, b, c = xbc[:, :di], xbc[:, di:di + n], xbc[:, di + n:]
    dt = jax.nn.softplus(dt + m["dt_bias"])
    y = ssd(xs.reshape(s, h, hp), dt, m["A_log"], b, c, m["D"], a.ssm_chunk,
            prec).reshape(s, di)
    y = rmsnorm(y * jax.nn.silu(z), m["norm"]["scale"], a.norm_eps)
    return act(prec, x + ein(prec, "se,ed->sd", y, m["out_proj"]))


def rope(x, theta):
    s, _, hd = x.shape
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def shared_block(bp, a: Arch, x, prec, q_block: int = 1024):
    """Causal multi-head attention with rotary positions, then SwiGLU;
    both pre-norm and residual. Queries go in blocks of ``q_block``."""
    s = x.shape[0]
    nh, nkv, hd = a.num_heads, a.num_kv_heads, a.head_dim
    at = bp["attn"]
    u = rmsnorm(x, bp["ln1"]["scale"], a.norm_eps)
    q = rope(ein(prec, "sd,de->se", u, at["wq"]).reshape(s, nh, hd),
             a.rope_theta)
    k = rope(ein(prec, "sd,de->se", u, at["wk"]).reshape(s, nkv, hd),
             a.rope_theta)
    v = ein(prec, "sd,de->se", u, at["wv"]).reshape(s, nkv, hd)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    qb = min(q_block, s)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
        sc = ein(prec, "qhe,khe->hqk", qi, k) / math.sqrt(hd)
        pos_q = i * qb + jnp.arange(qb)[:, None]
        mask = pos_q >= jnp.arange(s)[None, :]
        sc = jnp.where(mask[None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        return ein(prec, "hqk,khe->qhe", pr, v)

    o = jax.lax.map(jax.checkpoint(block), jnp.arange(s // qb))
    o = o.reshape(s, nh * hd)
    x = act(prec, x + ein(prec, "se,ed->sd", o, at["wo"]))
    m = bp["mlp"]
    u = rmsnorm(x, bp["ln2"]["scale"], a.norm_eps)
    hmid = (ein(prec, "sd,df->sf", u, m["wi"])
            * jax.nn.silu(ein(prec, "sd,df->sf", u, m["wg"])))
    return act(prec, x + ein(prec, "sf,fd->sd", hmid, m["wo"]))


def seq_loss(params, tokens, a: Arch, prec: str):
    """Mean next-token cross-entropy of one sequence (S,) int32."""
    x = act(prec, params["embed"][tokens].astype(jnp.float32))
    layer = jax.checkpoint(lambda h, lp: (mamba_layer(lp, a, h, prec), None))
    if a.family == "ssm":
        x, _ = jax.lax.scan(layer, x, params["blocks"])
    else:
        sb = params["shared_block"]
        blk = jax.checkpoint(lambda h: shared_block(sb, a, h, prec))

        def superblock(h, sbp):
            h, _ = jax.lax.scan(layer, h, sbp)
            return blk(h), None

        x, _ = jax.lax.scan(superblock, x, params["blocks"])
    x = rmsnorm(x, params["final_norm"]["scale"], a.norm_eps)
    if a.tie_embeddings:
        logits = ein(prec, "sd,vd->sv", x, params["embed"])
    else:
        logits = ein(prec, "sd,dv->sv", x, params["unembed"])
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    ll = jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]
    return -jnp.mean(ll)


@partial(jax.jit, static_argnames=("a", "prec"), donate_argnums=(2,))
def _seq_grad(params, tokens, acc, a: Arch, prec: str):
    p32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    loss, g = jax.value_and_grad(seq_loss)(p32, tokens, a, prec)
    return loss, jax.tree.map(jnp.add, acc, g)


def rank_grads(params, rows, a: Arch, prec: str, device=None):
    """Losses and summed f32 gradient over the sequences ``rows`` (R, S)
    of one rank, one sequence at a time, dispatched without waiting (so
    ranks on different devices run side by side)."""
    params = jax.device_put(params, device)
    acc = jax.device_put(
        jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        device)
    losses = []
    for r in rows:
        loss, acc = _seq_grad(params, jax.device_put(jnp.asarray(r), device),
                              acc, a, prec)
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
    """The reference's training state for ``ranks`` data-parallel ranks:
    parameters (configured dtype), per-rank error-feedback residuals and
    AdamW moments (float32), all on the host between steps."""

    def __init__(self, seed: int, a: Arch, o: Opt, ranks: int, prec: str):
        self.a, self.o, self.ranks, self.prec = a, o, ranks, prec
        self.seed = seed
        self.params = {k: np.asarray(v) for k, v in
                       flat(jax.jit(init_params, static_argnums=(0, 1))(
                           seed, a)).items()}
        self.tree = jax.tree.structure(
            jax.eval_shape(lambda: init_params(seed, a)))
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
        futs = [rank_grads(params, rows, a, self.prec,
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
            ax = model_axis(name, len(shape))
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
