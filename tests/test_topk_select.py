"""compress2d's per-bucket selection: bit-identical to the lax.top_k oracle
(kernels/bucket_topk/ref.py) on finite inputs, ties included; no sort or
TopK op in its program; well-formed on nan and inf."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import topk as topk_mod
from repro.kernels.bucket_topk.ref import bucket_topk_ref

B = 512
# x shapes: one bucket per row, one row of a model-sharded-like layout,
# and two leading batch axes (a replica axis over rows).
SHAPES = [(6, B), (1, 8, 3 * B), (2, 3, 2 * B)]


def _inputs(kind: str, shape, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if kind == "normal":
        return rng.standard_normal(shape).astype(np.float32)
    if kind == "few_values":          # heavy ties of magnitude and of value
        return rng.choice(np.float32([0, 0.5, -0.5, 1, -1]), size=shape)
    if kind == "zero_buckets":        # whole buckets of +0.0 and -0.0
        x = rng.standard_normal(shape).astype(np.float32)
        x[..., :B] = rng.choice(np.float32([0.0, -0.0]), size=x[..., :B].shape)
        return x
    if kind == "pm_pairs":            # +v and -v: equal magnitudes, mirrored
        half = rng.standard_normal(shape[:-1] + (shape[-1] // 2,))
        return np.concatenate([half, -half[..., ::-1]], -1).astype(np.float32)
    raise ValueError(kind)


def _bits(a) -> np.ndarray:
    return np.asarray(a).view(np.int32)


@pytest.mark.parametrize("kind", ["normal", "few_values", "zero_buckets",
                                  "pm_pairs"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("k", [1, 4, 8, 16, 64])
def test_compress2d_bit_identical_to_top_k_oracle(k, shape, kind):
    x = jnp.asarray(_inputs(kind, shape))
    stream, res = jax.jit(topk_mod.compress2d, static_argnums=(1, 2))(x, k, B)
    val, lidx, oracle_res = bucket_topk_ref(x.reshape(-1, B), k)
    m = shape[-1] // B
    assert stream.lidx.shape == (*shape[:-1], m, k)
    assert stream.lidx.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(stream.lidx).reshape(-1, k),
                                  np.asarray(lidx))
    np.testing.assert_array_equal(_bits(stream.val).reshape(-1, k), _bits(val))
    np.testing.assert_array_equal(_bits(res).reshape(-1, B),
                                  _bits(oracle_res))


_SORT_OR_TOPK = re.compile(r"\b(stablehlo|chlo|mhlo)\.(sort|top_?k)\b", re.I)


def test_guard_pattern_sees_sort_and_top_k():
    x = jax.ShapeDtypeStruct((8, B), jnp.float32)
    for fn in (lambda v: jax.lax.top_k(v, 4), lambda v: jnp.sort(v, -1)):
        assert _SORT_OR_TOPK.search(jax.jit(fn).lower(x).as_text())


@pytest.mark.parametrize("k", [4, 64])
def test_compress2d_lowers_without_sort(k):
    x = jax.ShapeDtypeStruct((1, 8, 16 * B), jnp.float32)
    text = jax.jit(topk_mod.compress2d, static_argnums=(1, 2)).lower(
        x, k, B).as_text()
    assert not _SORT_OR_TOPK.search(text), _SORT_OR_TOPK.findall(text)


@pytest.mark.parametrize("k", [4, 16])
def test_compress2d_nonfinite_bucket_is_well_formed(k):
    x = np.random.default_rng(1).standard_normal((2, 2 * B)).astype(np.float32)
    x[0, [3, 100, 101]] = [np.nan, np.inf, -np.inf]
    x[1, B + 7] = -np.nan
    x[1, B + 300] = np.inf
    stream, res = topk_mod.compress2d(jnp.asarray(x), k, B)
    lidx = np.asarray(stream.lidx)
    res = np.asarray(res).reshape(2, 2, B)
    xb = x.reshape(2, 2, B)
    for r in range(2):
        for b in range(2):
            row = lidx[r, b]
            assert np.all((row >= 0) & (row < B))
            assert np.all(np.diff(row) > 0)            # distinct, ascending
            sel = np.zeros(B, bool)
            sel[row] = True
            assert np.all(res[r, b][sel] == 0)
            np.testing.assert_array_equal(_bits(res[r, b][~sel]),
                                          _bits(xb[r, b][~sel]))
    # the nonfinite entries are the largest magnitudes: all are selected
    assert {3, 100, 101} <= set(lidx[0, 0].tolist())
    assert {7, 300} <= set(lidx[1, 1].tolist())
