"""Model FLOPs of one training token, from the configuration's shapes.

Counted, forward and backward (backward = twice forward), recomputation
not counted:

* matmuls: 6 x the weights that multiply activations: per Mamba-2 layer
  ``in_proj`` (d x (2 d_inner + 2 N + H)) and ``out_proj`` (d_inner x d);
  per shared attention + MLP invocation ``wq, wk, wv, wo`` and the three
  SwiGLU matrices; the output head over the padded vocabulary (d x V);
  the embedding lookup is not a matmul and is left out;
* the SSD scan of each Mamba-2 layer, as its chunked algorithm computes
  it with chunk Q (whole Q x Q blocks, the causal mask not subtracted):
  forward 2QN (C.B^T) + 2Q d_inner (the block times X) + 2N d_inner (chunk
  states) + 2N d_inner (output from entering states), times 3;
* attention scores and values per invocation, at sequence length S:
  forward 4 S (heads x head_dim), times 3, the causal half not
  subtracted (the PaLM convention).

The depthwise convolution, norms, gates and softmax are elementwise and
not counted.
"""
from __future__ import annotations


def mamba_layer_weights(d: int, d_inner: int, n: int, h: int) -> int:
    return d * (2 * d_inner + 2 * n + h) + d_inner * d


def ssd_forward(q: int, n: int, d_inner: int) -> int:
    return 2 * q * n + 2 * q * d_inner + 4 * n * d_inner


def flops_per_token(m: dict, seq_len: int) -> dict:
    """Terms and total for the configuration file's ``model`` block."""
    d = m["d_model"]
    di = m["ssm_expand"] * d
    n = m["ssm_state"]
    h = di // m["ssm_head_dim"]
    layers = m["num_layers"]
    vocab = -(-m["vocab_size"] // 256) * 256
    mats = layers * mamba_layer_weights(d, di, n, h) + d * vocab
    attn_calls = layers // m["attn_every"] if m.get("attn_every") else 0
    attn_width = m.get("num_heads", 0) * m.get("head_dim", 0)
    if attn_calls:
        kv = m["num_kv_heads"] * m["head_dim"]
        shared = 2 * d * attn_width + 2 * d * kv + 3 * d * m["d_ff"]
        mats += attn_calls * shared
    terms = {
        "matmul": 6 * mats,
        "ssd": 3 * layers * ssd_forward(m["ssm_chunk"], n, di),
        "attention": 3 * attn_calls * 4 * seq_len * attn_width,
    }
    terms["total"] = sum(terms.values())
    return terms
