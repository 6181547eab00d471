"""Plain reference of Mamba-2 (arXiv:2405.21060), for configurations whose
file names ``"reference": "mamba2"``.

The model: an embedding, residual Mamba-2 layers (pre-norm, SSD written
out as a chunked scan), a final norm and the output head over the padded
vocabulary; next-token cross-entropy. Written in straightforward
``jax.numpy`` from the paper, independent of the program under test; the
contractions, the float8 control and the sync are ``bench/reference.py``'s.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, fields

import jax
import jax.numpy as jnp

from bench.reference import act, ein, normal, rmsnorm


@dataclass(frozen=True)
class Arch:
    """The sizes the reference needs, read from the configuration file."""
    num_layers: int
    d_model: int
    vocab_size: int
    ssm_state: int
    ssm_expand: int
    ssm_head_dim: int
    ssm_chunk: int
    conv_width: int
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
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


def arch(model: dict) -> Arch:
    """The :class:`Arch` of a configuration file's ``model`` block."""
    names = {f.name for f in fields(Arch)}
    return Arch(**{k: v for k, v in model.items() if k in names})


# ------------------------------------------------------------ weights

def _mamba_layer(key, a: Arch) -> dict:
    d, di, n, h = a.d_model, a.d_inner, a.ssm_state, a.ssm_heads
    ks = jax.random.split(key, 5)
    conv_dim = di + 2 * n
    return {
        "ln": {"scale": jnp.ones((d,), a.pdtype)},
        "mixer": {
            "in_proj": normal(ks[0], (d, 2 * di + 2 * n + h),
                              1 / math.sqrt(d), a.pdtype),
            "conv_w": normal(ks[1], (a.conv_width, conv_dim),
                             1 / math.sqrt(a.conv_width), a.pdtype),
            "conv_b": jnp.zeros((conv_dim,), a.pdtype),
            "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
            "D": jnp.ones((h,), jnp.float32),
            "dt_bias": jnp.zeros((h,), jnp.float32),
            "norm": {"scale": jnp.ones((di,), a.pdtype)},
            "out_proj": normal(ks[2], (di, d),
                               1 / math.sqrt(di * 2 * a.num_layers), a.pdtype),
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
    p = {"embed": normal(ks[0], (v, d), 0.02, a.pdtype),
         "final_norm": {"scale": jnp.ones((d,), a.pdtype)}}
    if not a.tie_embeddings:
        p["unembed"] = normal(ks[1], (d, v), d ** -0.5, a.pdtype)
    p["blocks"] = jax.vmap(lambda k: _mamba_layer(k, a))(
        jax.random.split(ks[2], a.num_layers))
    return p


# ------------------------------------------------------------ model

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


def seq_loss(params, tokens, a: Arch, prec: str):
    """Mean next-token cross-entropy of one sequence (S,) int32."""
    x = act(prec, params["embed"][tokens].astype(jnp.float32))
    layer = jax.checkpoint(lambda h, lp: (mamba_layer(lp, a, h, prec), None))
    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = rmsnorm(x, params["final_norm"]["scale"], a.norm_eps)
    if a.tie_embeddings:
        logits = ein(prec, "sd,vd->sv", x, params["embed"])
    else:
        logits = ein(prec, "sd,dv->sv", x, params["unembed"])
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    ll = jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]
    return -jnp.mean(ll)


# ------------------------------------------------------------ FLOPs

def flops_per_token(model: dict, seq_len: int) -> dict:
    """Model FLOPs of one training token for a ``model`` block, forward
    and backward (backward = twice forward), recomputation not counted:

    * ``matmul``: 6 x the weights that multiply activations: per layer
      ``in_proj`` (d x (2 d_inner + 2 N + H)) and ``out_proj``
      (d_inner x d); the output head over the padded vocabulary (d x V);
      the embedding lookup is not a matmul and is left out;
    * ``ssd``: the SSD scan of each layer as its chunked algorithm
      computes it with chunk Q (whole Q x Q blocks, the causal mask not
      subtracted): forward 2QN (C.B^T) + 2Q d_inner (the block times X)
      + 2N d_inner (chunk states) + 2N d_inner (output from entering
      states), times 3.

    The depthwise convolution, norms, gates and softmax are elementwise
    and not counted. The SSD term does not depend on ``seq_len``."""
    a = arch(model)
    d, di, n, h = a.d_model, a.d_inner, a.ssm_state, a.ssm_heads
    layer = d * (2 * di + 2 * n + h) + di * d
    ssd_fwd = 2 * a.ssm_chunk * n + 2 * a.ssm_chunk * di + 4 * n * di
    terms = {"matmul": 6 * (a.num_layers * layer + d * a.padded_vocab),
             "ssd": 3 * a.num_layers * ssd_fwd}
    terms["total"] = sum(terms.values())
    return terms


# ------------------------------------------------------------ test cases

# A tiny cell of this architecture for the benchmark's own tests on the
# CPU: the program's configuration module, and a ``model`` block in
# float32 (the tests also pass it to the program as overrides).
TINY = {"arch": "mamba2_370m",
        "model": {"family": "ssm", "num_layers": 2, "d_model": 64,
                  "vocab_size": 512, "ssm_state": 16, "ssm_expand": 2,
                  "ssm_head_dim": 16, "ssm_chunk": 8, "conv_width": 4,
                  "tie_embeddings": True, "norm_eps": 1e-5,
                  "dtype": "float32", "param_dtype": "float32"}}

# flops_per_token against a hand count: (model block, seq_len, terms).
# The program's smoke sizes: d 64, d_inner 128, N 16, H 8, 4 layers, Q 8,
# vocab 512. in_proj 64 x (2*128 + 2*16 + 8) = 18,944; out_proj 128 x 64
# = 8,192; 4 layers: 108,544; head 64 x 512 = 32,768 -> 141,312 weights,
# x 6 = 847,872. SSD forward per layer 2*8*16 + 2*8*128 + 4*16*128 =
# 10,496; x 3 x 4 = 125,952.
FLOPS_CASES = [
    ({**TINY["model"], "num_layers": 4}, 32,
     {"matmul": 847_872, "ssd": 125_952, "total": 973_824}),
]
