"""Model assembly: stacked-layer scan, per-family blocks, train/prefill/decode.

Layer parameters are STACKED on a leading axis and consumed by
``jax.lax.scan`` so compile time and HLO size are O(1) in depth (critical
for the 126-layer 405B dry-run). Mixed-layout families scan over
*superblocks*:

  vlm    (llama-3.2-vision): superblock = (cross_attn_every-1) self layers
         + 1 gated cross-attention layer; nested scan.
  hybrid (zamba2): superblock = attn_every mamba layers + one invocation of
         the SHARED attention+MLP block (params reused across invocations,
         zamba2's signature trick); each invocation site keeps its own KV
         cache at decode time.

Decode state is a pytree of stacked per-layer caches; entry points:
  forward_train(params, batch)          -> logits
  loss_fn(params, batch)                -> scalar CE
  prefill(params, batch, cache_len)     -> (last_logits, state)
  decode_step(params, state, tokens)    -> (logits, state')
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models import layers as L
from repro.models.layers import KVCache
from repro.models import moe as moe_mod
from repro.models import mamba2 as ssm_mod


# ==========================================================================
# Parameter initialization
# ==========================================================================

def _stack_init(fn, key, n: int):
    """vmap an init over n layers -> leaves with leading axis n."""
    return jax.vmap(fn)(jax.random.split(key, n))


def _dense_block_init(key, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": L.rmsnorm_init(cfg.d_model, cfg.param_dtype),
        "attn": L.attn_init(k1, cfg),
        "ln2": L.rmsnorm_init(cfg.d_model, cfg.param_dtype),
    }
    if cfg.family == "moe":
        p["moe"] = moe_mod.moe_init(k2, cfg)
    else:
        p["mlp"] = L.mlp_init(k2, cfg)
    return p


def _mamba_block_init(key, cfg: ModelConfig) -> dict:
    return {
        "ln": L.rmsnorm_init(cfg.d_model, cfg.param_dtype),
        "mixer": ssm_mod.mamba_init(key, cfg),
    }


def _cross_block_init(key, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": L.rmsnorm_init(cfg.d_model, cfg.param_dtype),
        "xattn": L.cross_attn_init(k1, cfg),
        "ln2": L.rmsnorm_init(cfg.d_model, cfg.param_dtype),
        "mlp": L.mlp_init(k2, cfg),
        "mlp_gate": jnp.zeros((), cfg.param_dtype),
    }


def init_params(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 8)
    d, v = cfg.d_model, cfg.padded_vocab
    params: dict = {
        "embed": (jax.random.normal(ks[0], (v, d), jnp.float32) * 0.02).astype(cfg.param_dtype),
        "final_norm": L.rmsnorm_init(d, cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = (jax.random.normal(ks[1], (d, v), jnp.float32)
                             * (d ** -0.5)).astype(cfg.param_dtype)

    fam = cfg.family
    if fam in ("dense", "moe"):
        params["blocks"] = _stack_init(
            lambda k: _dense_block_init(k, cfg), ks[2], cfg.num_layers)
    elif fam == "encoder":
        params["blocks"] = _stack_init(
            lambda k: _dense_block_init(k, cfg), ks[2], cfg.num_layers)
        params["frontend_proj"] = L._dense_init(
            ks[3], (cfg.frontend_dim or d, d), cfg.param_dtype)
        params["pos_embed"] = (jax.random.normal(ks[4], (cfg.max_seq_len, d), jnp.float32)
                               * 0.02).astype(cfg.param_dtype)
    elif fam == "ssm":
        params["blocks"] = _stack_init(
            lambda k: _mamba_block_init(k, cfg), ks[2], cfg.num_layers)
    elif fam == "hybrid":
        nsb = cfg.num_layers // cfg.attn_every
        params["blocks"] = _stack_init(
            lambda k: _stack_init(lambda k2: _mamba_block_init(k2, cfg), k, cfg.attn_every),
            ks[2], nsb)
        params["shared_block"] = _dense_block_init(ks[3], cfg)
    elif fam == "vlm":
        every = cfg.cross_attn_every
        nsb = cfg.num_layers // every
        params["blocks"] = {
            "selfs": _stack_init(
                lambda k: _stack_init(lambda k2: _dense_block_init(k2, cfg), k, every - 1),
                ks[2], nsb),
            "cross": _stack_init(lambda k: _cross_block_init(k, cfg), ks[3], nsb),
        }
        params["vision_proj"] = L._dense_init(
            ks[4], (cfg.vision_dim, d), cfg.param_dtype)
    else:
        raise ValueError(fam)
    return params


# ==========================================================================
# Block application (single layer, unstacked params)
# ==========================================================================

def _dense_block(p, cfg: ModelConfig, x, positions, causal=True):
    h = L.attention(p["attn"], cfg, L.rmsnorm(p["ln1"], x, cfg.norm_eps),
                    positions, causal=causal)
    x = x + h
    z = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if cfg.family == "moe":
        b, s, d = z.shape
        f = moe_mod.moe_apply(p["moe"], cfg, z.reshape(b * s, d)).reshape(b, s, d)
    else:
        f = L.mlp(p["mlp"], cfg, z)
    return x + f


def _mamba_block(p, cfg: ModelConfig, x):
    h, state = ssm_mod.mamba_apply(p["mixer"], cfg, L.rmsnorm(p["ln"], x, cfg.norm_eps))
    return x + h, state


def _cross_block(p, cfg: ModelConfig, x, kv_feats):
    h = L.cross_attention(p["xattn"], cfg, L.rmsnorm(p["ln1"], x, cfg.norm_eps), kv_feats)
    x = x + h
    g = jnp.tanh(p["mlp_gate"].astype(jnp.float32)).astype(x.dtype)
    f = L.mlp(p["mlp"], cfg, L.rmsnorm(p["ln2"], x, cfg.norm_eps))
    return x + g * f


# ==========================================================================
# Full forward (training / encoder inference)
# ==========================================================================

def _maybe_remat(cfg: ModelConfig):
    """Decorator factory: jax.checkpoint when cfg.remat else identity."""
    return jax.checkpoint if cfg.remat else (lambda fn: fn)


def _cb(x, cfg: ModelConfig):
    """Constrain activation batch sharding to the dp axes (auto-SPMD mode).

    GSPMD loses the batch sharding after embedding gathers / loss gathers
    (found via dry-run HLO: batch-replicated f32 score tensors). A bare
    PartitionSpec constraint uses the ambient mesh; no-op when
    cfg.act_dp_axes is None (shard_map manual-dp context or smoke tests).
    """
    if not cfg.act_dp_axes:
        return x
    from jax.sharding import PartitionSpec as P, get_abstract_mesh

    axes = list(cfg.act_dp_axes)
    mesh_shape = dict(get_abstract_mesh().shape)
    # drop leading dp axes until the batch dim divides evenly (microbatches
    # can be narrower than pod x data)
    import numpy as _np
    while axes and mesh_shape and x.shape[0] % int(
            _np.prod([mesh_shape.get(a, 1) for a in axes])):
        axes.pop(0)
    if not axes:
        return x
    spec = P(tuple(axes), *([None] * (x.ndim - 1)))
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError, TypeError):
        return x


def _cv(logits, cfg: ModelConfig):
    """Constrain logits' vocab axis over 'model'.

    Tied-embedding models otherwise materialize REPLICATED (B,S,V) f32
    logits after the d-contraction psum (found via dry-run HLO: 6x13GB
    tensors dominating mamba2's memory term). Works in both auto mode
    (dp axes + model) and inside shard_map (model is the auto axis).
    """
    from jax.sharding import PartitionSpec as P
    dp = tuple(cfg.act_dp_axes) if cfg.act_dp_axes else None
    spec = P(dp, *([None] * (logits.ndim - 2)), "model")
    try:
        return jax.lax.with_sharding_constraint(logits, spec)
    except (ValueError, RuntimeError, TypeError):
        return logits


def forward(params, cfg: ModelConfig, batch: dict) -> jax.Array:
    """batch: {'tokens': (B,S) int32} (+ 'image_embeds' vlm, 'frames' encoder).
    Returns logits (B, S, V)."""
    fam = cfg.family
    if fam == "encoder":
        frames = batch["frames"]  # (B, S, frontend_dim) — stub frontend output
        x = frames.astype(cfg.dtype) @ params["frontend_proj"]
        s = x.shape[1]
        x = x + params["pos_embed"][:s][None]
    else:
        tokens = batch["tokens"]
        # The GSPMD gather partitioner mishandles sharded-indices +
        # offset-sharded-operand (verifier failure on the 2x16x16 mesh);
        # replicating the (tiny, i32) indices makes it a clean local
        # gather of each device's d-slice. _cb re-shards the output.
        if cfg.act_dp_axes:
            from jax.sharding import PartitionSpec as _P
            try:
                tokens = jax.lax.with_sharding_constraint(tokens, _P())
            except (ValueError, RuntimeError, TypeError):
                pass
        x = params["embed"][tokens].astype(cfg.dtype)
        s = x.shape[1]
    x = _cb(x, cfg)
    positions = jnp.arange(s, dtype=jnp.int32)

    if fam in ("dense", "moe", "encoder"):
        causal = cfg.is_decoder

        def body(h, lp):
            h = _cb(h, cfg)
            return _maybe_remat(cfg)(
                lambda hh: _dense_block(lp, cfg, hh, positions, causal=causal)
            )(h), None

        x, _ = jax.lax.scan(body, x, params["blocks"])

    elif fam == "ssm":
        def body(h, lp):
            h = _cb(h, cfg)
            out, _state = _maybe_remat(cfg)(lambda hh: _mamba_block(lp, cfg, hh))(h)
            return out, None

        x, _ = jax.lax.scan(body, x, params["blocks"])

    elif fam == "hybrid":
        shared = params["shared_block"]

        def inner(h, lp):
            h = _cb(h, cfg)
            out, _ = _maybe_remat(cfg)(lambda hh: _mamba_block(lp, cfg, hh))(h)
            return out, None

        def superblock(h, sbp):
            h = _cb(h, cfg)
            h, _ = jax.lax.scan(inner, h, sbp)
            h = _maybe_remat(cfg)(
                lambda hh: _dense_block(shared, cfg, hh, positions, causal=True)
            )(h)
            return h, None

        x, _ = jax.lax.scan(superblock, x, params["blocks"])

    elif fam == "vlm":
        kv_feats = (batch["image_embeds"].astype(cfg.dtype)
                    @ params["vision_proj"])

        def inner(h, lp):
            h = _cb(h, cfg)
            return _maybe_remat(cfg)(
                lambda hh: _dense_block(lp, cfg, hh, positions, causal=True)
            )(h), None

        def superblock(h, sbp):
            h = _cb(h, cfg)
            h, _ = jax.lax.scan(inner, h, sbp["selfs"])
            h = _maybe_remat(cfg)(
                lambda hh: _cross_block(sbp["cross"], cfg, hh, kv_feats)
            )(h)
            return h, None

        x, _ = jax.lax.scan(superblock, x, params["blocks"])
    else:
        raise ValueError(fam)

    x = L.rmsnorm(params["final_norm"], _cb(x, cfg), cfg.norm_eps)
    unembed = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    logits = (x @ unembed.astype(cfg.dtype)).astype(jnp.float32)
    return _cv(logits, cfg)


def loss_fn(params, cfg: ModelConfig, batch: dict) -> jax.Array:
    """Mean next-token (decoder) or per-frame (encoder) cross-entropy."""
    logits = forward(params, cfg, batch)
    labels = batch["labels"]
    if cfg.is_decoder:
        logits, labels = logits[:, :-1], labels[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


# ==========================================================================
# Serving: prefill + decode
# ==========================================================================

class DecodeState(NamedTuple):
    pos: jax.Array                 # scalar int32: next position to write
    kv: Any = None                 # stacked KVCache (L_attn leading)
    cross_kv: Any = None           # vlm: stacked (nsb, ...) K/V of image tokens
    conv: Any = None               # ssm: (L, B, W-1, conv_dim)
    ssm: Any = None                # ssm: (L, B, H, P, N)


def _attn_cache_width(cfg: ModelConfig, cache_len: int) -> int:
    return min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len


def prefill(params, cfg: ModelConfig, batch: dict, cache_len: int):
    """Run the prompt, return (last-token logits (B,V), DecodeState)."""
    fam = cfg.family
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = params["embed"][tokens].astype(cfg.dtype)
    positions = jnp.arange(s, dtype=jnp.int32)
    w = _attn_cache_width(cfg, cache_len)

    kv = cross_kv = conv = ssm_states = None

    if fam in ("dense", "moe"):
        def body(h, lp):
            hn = L.rmsnorm(lp["ln1"], h, cfg.norm_eps)
            a, cache = L.attention_prefill(lp["attn"], cfg, hn, positions, cache_len)
            h = h + a
            z = L.rmsnorm(lp["ln2"], h, cfg.norm_eps)
            if cfg.family == "moe":
                bb, ss, dd = z.shape
                f = moe_mod.moe_apply(lp["moe"], cfg, z.reshape(bb * ss, dd)).reshape(bb, ss, dd)
            else:
                f = L.mlp(lp["mlp"], cfg, z)
            return h + f, cache

        x, kv = jax.lax.scan(body, x, params["blocks"])

    elif fam == "ssm":
        def body(h, lp):
            hn = L.rmsnorm(lp["ln"], h, cfg.norm_eps)
            out, state = ssm_mod.mamba_apply(lp["mixer"], cfg, hn)
            # conv tail: last (W-1) conv inputs
            zxbcdt = hn @ lp["mixer"]["in_proj"]
            di, n = cfg.d_inner, cfg.ssm_state
            conv_in = zxbcdt[..., di:2 * di + 2 * n]
            tail = conv_in[:, -(cfg.conv_width - 1):, :]
            return h + out, (state, tail)

        x, (ssm_states, conv) = jax.lax.scan(body, x, params["blocks"])

    elif fam == "hybrid":
        shared = params["shared_block"]

        def inner(h, lp):
            hn = L.rmsnorm(lp["ln"], h, cfg.norm_eps)
            out, state = ssm_mod.mamba_apply(lp["mixer"], cfg, hn)
            zxbcdt = hn @ lp["mixer"]["in_proj"]
            di, n = cfg.d_inner, cfg.ssm_state
            tail = (zxbcdt[..., di:2 * di + 2 * n])[:, -(cfg.conv_width - 1):, :]
            return h + out, (state, tail)

        def superblock(h, sbp):
            h, states = jax.lax.scan(inner, h, sbp)
            hn = L.rmsnorm(shared["ln1"], h, cfg.norm_eps)
            a, cache = L.attention_prefill(shared["attn"], cfg, hn, positions, cache_len)
            h = h + a
            h = h + L.mlp(shared["mlp"], cfg, L.rmsnorm(shared["ln2"], h, cfg.norm_eps))
            return h, (states, cache)

        x, ((ssm_states, conv), kv) = jax.lax.scan(superblock, x, params["blocks"])
        # flatten (nsb, every, ...) -> (L, ...)
        ssm_states = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), ssm_states)
        conv = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), conv)

    elif fam == "vlm":
        kv_feats = batch["image_embeds"].astype(cfg.dtype) @ params["vision_proj"]
        nkv, hd = cfg.num_kv_heads, cfg.head_dim
        t = kv_feats.shape[1]

        def superblock(h, sbp):
            def inner(hh, lp):
                hn = L.rmsnorm(lp["ln1"], hh, cfg.norm_eps)
                a, cache = L.attention_prefill(lp["attn"], cfg, hn, positions, cache_len)
                hh = hh + a
                hh = hh + L.mlp(lp["mlp"], cfg, L.rmsnorm(lp["ln2"], hh, cfg.norm_eps))
                return hh, cache

            h, caches = jax.lax.scan(inner, h, sbp["selfs"])
            cp = sbp["cross"]
            h = _cross_block(cp, cfg, h, kv_feats)
            # cache image K/V for decode (static across steps)
            k_img = (kv_feats @ cp["xattn"]["wk"]).reshape(b, t, nkv, hd)
            k_img = L.rmsnorm(cp["xattn"]["k_norm"], k_img, cfg.norm_eps)
            v_img = (kv_feats @ cp["xattn"]["wv"]).reshape(b, t, nkv, hd)
            return h, (caches, (k_img, v_img))

        x, (kv, cross_kv) = jax.lax.scan(superblock, x, params["blocks"])
    else:
        raise ValueError(fam)

    x = L.rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    unembed = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    logits = (x @ unembed.astype(cfg.dtype))[:, 0].astype(jnp.float32)
    return logits, DecodeState(
        pos=jnp.asarray(s, jnp.int32), kv=kv, cross_kv=cross_kv,
        conv=conv, ssm=ssm_states,
    )


def init_decode_state(cfg: ModelConfig, batch_size: int, cache_len: int,
                      prefix_len: int = 0) -> DecodeState:
    """Empty decode state (for dry-running serve_step without a prefill)."""
    b = batch_size
    w = _attn_cache_width(cfg, cache_len)
    nkv, hd = cfg.num_kv_heads, cfg.head_dim
    kv = cross_kv = conv = ssm_states = None
    dt = cfg.dtype
    if cfg.family in ("dense", "moe"):
        kv = KVCache(
            jnp.zeros((cfg.num_layers, b, w, nkv, hd), dt),
            jnp.zeros((cfg.num_layers, b, w, nkv, hd), dt),
        )
    elif cfg.family == "ssm":
        conv_dim = cfg.d_inner + 2 * cfg.ssm_state
        conv = jnp.zeros((cfg.num_layers, b, cfg.conv_width - 1, conv_dim), dt)
        ssm_states = jnp.zeros(
            (cfg.num_layers, b, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
            jnp.float32)
    elif cfg.family == "hybrid":
        nsb = cfg.num_layers // cfg.attn_every
        conv_dim = cfg.d_inner + 2 * cfg.ssm_state
        conv = jnp.zeros((cfg.num_layers, b, cfg.conv_width - 1, conv_dim), dt)
        ssm_states = jnp.zeros(
            (cfg.num_layers, b, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
            jnp.float32)
        kv = KVCache(
            jnp.zeros((nsb, b, w, nkv, hd), dt),
            jnp.zeros((nsb, b, w, nkv, hd), dt),
        )
    elif cfg.family == "vlm":
        every = cfg.cross_attn_every
        nsb = cfg.num_layers // every
        kv = KVCache(
            jnp.zeros((nsb, every - 1, b, w, nkv, hd), dt),
            jnp.zeros((nsb, every - 1, b, w, nkv, hd), dt),
        )
        cross_kv = (
            jnp.zeros((nsb, b, cfg.num_image_tokens, nkv, hd), dt),
            jnp.zeros((nsb, b, cfg.num_image_tokens, nkv, hd), dt),
        )
    return DecodeState(pos=jnp.asarray(prefix_len, jnp.int32), kv=kv,
                       cross_kv=cross_kv, conv=conv, ssm=ssm_states)


def decode_step(params, cfg: ModelConfig, state: DecodeState, tokens: jax.Array,
                moe_serve=None):
    """One autoregressive step. tokens: (B, 1) -> (logits (B,V), state').

    ``state.pos`` may be a scalar (batch-synchronous decode) or a (B,)
    per-slot position vector (continuous batching — see attention_decode).
    ``moe_serve``: an optional :class:`repro.models.moe.ServeDispatch`;
    when given, MoE layers route through the serve-time dispatch (active-
    slot masking + planned combine exchange, DESIGN.md §8) instead of the
    training-style :func:`moe_apply`."""
    fam = cfg.family
    assert cfg.is_decoder, "encoder-only archs have no decode step"
    x = params["embed"][tokens].astype(cfg.dtype)
    pos = state.pos
    kv = cross_kv = conv = ssm_states = None

    if fam in ("dense", "moe"):
        def body(h, inp):
            lp, cache = inp
            hn = L.rmsnorm(lp["ln1"], h, cfg.norm_eps)
            a, cache = L.attention_decode(lp["attn"], cfg, hn, cache, pos)
            h = h + a
            z = L.rmsnorm(lp["ln2"], h, cfg.norm_eps)
            if cfg.family == "moe":
                bb, ss, dd = z.shape
                z2 = z.reshape(bb * ss, dd)
                if moe_serve is not None:
                    f = moe_mod.moe_apply_serve(lp["moe"], cfg, z2, moe_serve)
                else:
                    f = moe_mod.moe_apply(lp["moe"], cfg, z2)
                f = f.reshape(bb, ss, dd)
            else:
                f = L.mlp(lp["mlp"], cfg, z)
            return h + f, cache

        x, kv = jax.lax.scan(body, x, (params["blocks"], state.kv))

    elif fam == "ssm":
        def body(h, inp):
            lp, cv, st = inp
            hn = L.rmsnorm(lp["ln"], h, cfg.norm_eps)
            out, cv, st = ssm_mod.mamba_decode(lp["mixer"], cfg, hn, cv, st)
            return h + out, (cv, st)

        x, (conv, ssm_states) = jax.lax.scan(
            body, x, (params["blocks"], state.conv, state.ssm))

    elif fam == "hybrid":
        shared = params["shared_block"]
        every = cfg.attn_every
        nsb = cfg.num_layers // every
        conv_s = state.conv.reshape((nsb, every) + state.conv.shape[1:])
        ssm_s = state.ssm.reshape((nsb, every) + state.ssm.shape[1:])

        def inner(h, inp):
            lp, cv, st = inp
            hn = L.rmsnorm(lp["ln"], h, cfg.norm_eps)
            out, cv, st = ssm_mod.mamba_decode(lp["mixer"], cfg, hn, cv, st)
            return h + out, (cv, st)

        def superblock(h, inp):
            sbp, cv, st, cache = inp
            h, (cv, st) = jax.lax.scan(inner, h, (sbp, cv, st))
            hn = L.rmsnorm(shared["ln1"], h, cfg.norm_eps)
            a, cache = L.attention_decode(shared["attn"], cfg, hn, cache, pos)
            h = h + a
            h = h + L.mlp(shared["mlp"], cfg, L.rmsnorm(shared["ln2"], h, cfg.norm_eps))
            return h, (cv, st, cache)

        x, (conv, ssm_states, kv) = jax.lax.scan(
            superblock, x, (params["blocks"], conv_s, ssm_s, state.kv))
        conv = conv.reshape((-1,) + conv.shape[2:])
        ssm_states = ssm_states.reshape((-1,) + ssm_states.shape[2:])

    elif fam == "vlm":
        def superblock(h, inp):
            sbp, cache, (k_img, v_img) = inp

            def inner(hh, inp2):
                lp, c = inp2
                hn = L.rmsnorm(lp["ln1"], hh, cfg.norm_eps)
                a, c = L.attention_decode(lp["attn"], cfg, hn, c, pos)
                hh = hh + a
                hh = hh + L.mlp(lp["mlp"], cfg, L.rmsnorm(lp["ln2"], hh, cfg.norm_eps))
                return hh, c

            h, cache = jax.lax.scan(inner, h, (sbp["selfs"], cache))
            # cross-attention against the cached image K/V
            cp = sbp["cross"]
            hn = L.rmsnorm(cp["ln1"], h, cfg.norm_eps)
            bq, sq = hn.shape[0], hn.shape[1]
            nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
            q = (hn @ cp["xattn"]["wq"]).reshape(bq, sq, nh, hd)
            q = L.rmsnorm(cp["xattn"]["q_norm"], q, cfg.norm_eps)
            t = k_img.shape[1]
            mask = jnp.ones((bq, 1, 1, sq, t), bool)
            a = L._sdpa(q, k_img, v_img, mask, hd) @ cp["xattn"]["wo"]
            gate = jnp.tanh(cp["xattn"]["gate"].astype(jnp.float32)).astype(h.dtype)
            h = h + gate * a
            g2 = jnp.tanh(cp["mlp_gate"].astype(jnp.float32)).astype(h.dtype)
            h = h + g2 * L.mlp(cp["mlp"], cfg, L.rmsnorm(cp["ln2"], h, cfg.norm_eps))
            return h, cache

        x, kv = jax.lax.scan(
            superblock, x, (params["blocks"], state.kv, state.cross_kv))
        cross_kv = state.cross_kv
    else:
        raise ValueError(fam)

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    unembed = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    logits = (x @ unembed.astype(cfg.dtype))[:, 0].astype(jnp.float32)
    return logits, DecodeState(pos=pos + 1, kv=kv, cross_kv=cross_kv,
                               conv=conv, ssm=ssm_states)


# ==========================================================================
# Public façade
# ==========================================================================

class Model:
    """Thin façade bundling config + pure functions (no state)."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    def init(self, key):
        return init_params(key, self.cfg)

    def forward(self, params, batch):
        return forward(params, self.cfg, batch)

    def loss(self, params, batch):
        return loss_fn(params, self.cfg, batch)

    def prefill(self, params, batch, cache_len: int):
        return prefill(params, self.cfg, batch, cache_len)

    def decode_step(self, params, state, tokens, moe_serve=None):
        return decode_step(params, self.cfg, state, tokens,
                           moe_serve=moe_serve)

    def init_decode_state(self, batch_size: int, cache_len: int, prefix_len: int = 0):
        return init_decode_state(self.cfg, batch_size, cache_len, prefix_len)


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
