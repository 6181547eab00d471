"""HLO cost parser + roofline model validation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.utils.hlo_cost import total_cost
from repro.utils.roofline import Roofline, model_flops_train


def test_loop_free_flops_match_cost_analysis():
    @jax.jit
    def f(a, b, c):
        return (a @ b) @ c

    comp = f.lower(jax.ShapeDtypeStruct((256, 512), jnp.float32),
                   jax.ShapeDtypeStruct((512, 1024), jnp.float32),
                   jax.ShapeDtypeStruct((1024, 128), jnp.float32)).compile()
    mc = total_cost(comp.as_text())
    np.testing.assert_allclose(mc.flops, comp.cost_analysis()["flops"], rtol=1e-6)


def test_scan_trip_count_multiplies():
    @jax.jit
    def g(x, ws):
        def body(h, w):
            return h @ w, None
        return jax.lax.scan(body, x, ws)[0]

    comp = g.lower(jax.ShapeDtypeStruct((256, 256), jnp.float32),
                   jax.ShapeDtypeStruct((10, 256, 256), jnp.float32)).compile()
    mc = total_cost(comp.as_text())
    np.testing.assert_allclose(mc.flops, 10 * 2 * 256 ** 3, rtol=1e-6)
    assert any(t == 10 for _, t in mc.trip_counts)
    # XLA's own analysis counts the body once — we must exceed it
    assert mc.flops > comp.cost_analysis()["flops"] * 5


def test_collective_bytes_psum(mesh4x2):
    def h(x):
        return jax.lax.psum(x, "data")

    m = jax.jit(shard_map(h, mesh=mesh4x2, in_specs=P("data"),
                          out_specs=P(), check_vma=False))
    comp = m.lower(jax.ShapeDtypeStruct((64, 128), jnp.float32)).compile()
    mc = total_cost(comp.as_text())
    # all-reduce of a (16,128) f32 shard = 8192B -> ring 2*(3/4)*8192
    np.testing.assert_allclose(mc.coll_by_kind["all-reduce"], 12288.0, rtol=1e-6)


def test_nested_scan_multiplies():
    @jax.jit
    def g(x, ws):
        def outer(h, _):
            def inner(h2, w):
                return h2 @ w, None
            return jax.lax.scan(inner, h, ws)[0], None
        return jax.lax.scan(outer, x, None, length=3)[0]

    comp = g.lower(jax.ShapeDtypeStruct((128, 128), jnp.float32),
                   jax.ShapeDtypeStruct((5, 128, 128), jnp.float32)).compile()
    mc = total_cost(comp.as_text())
    np.testing.assert_allclose(mc.flops, 15 * 2 * 128 ** 3, rtol=1e-6)


def test_roofline_terms_and_dominance():
    r = Roofline(flops=197e12 * 256, hbm_bytes=819e9 * 256 * 2,
                 coll_bytes_per_chip=50e9, chips=256,
                 model_flops=197e12 * 256 * 0.5)
    assert abs(r.t_compute - 1.0) < 1e-9
    assert abs(r.t_memory - 2.0) < 1e-9
    assert abs(r.t_collective - 0.5) < 1e-9
    assert r.dominant == "memory"
    assert r.bound == 2.0
    assert abs(r.serial_bound - 3.5) < 1e-9
    assert abs(r.useful_flops_ratio - 0.5) < 1e-9
    assert abs(r.mfu_bound - 0.25) < 1e-9


def test_model_flops():
    assert model_flops_train(1e9, 1e6) == 6e15
