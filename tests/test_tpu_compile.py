"""The four Pallas kernels compile for a TPU v5e chip (``interpret=False``).

Nothing runs: the chip is described, not attached, and XLA's TPU compiler
refuses here what the chip would refuse (unaligned blocks, ops the kernel
compiler cannot lower). The widths are the mamba2-370m sync plan's on one
chip: 512-wide top-k buckets with k=4 and 1024-wide 4-bit QSGD buckets.
Its flat group has 1,380 top-k buckets and 690 QSGD buckets, neither a
multiple of 8; its first model-row group has 2,048 and 1,024.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bucket_scatter.kernel import bucket_scatter_pallas
from repro.kernels.bucket_topk.kernel import bucket_topk_pallas
from repro.kernels.qsgd_pack.kernel import qsgd_pack_pallas
from repro.kernels.qsgd_unpack.kernel import qsgd_unpack_pallas

B, K, BITS, BQ = 512, 4, 4, 1024
W = BQ * BITS // 32
# (top-k bucket rows, QSGD bucket rows): unaligned, then aligned
ROWS = {"unaligned": (1380, 690), "aligned": (2048, 1024)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_call(name, nb, nbq, sds):
    if name == "bucket_topk":
        return (lambda x: bucket_topk_pallas(x, K, interpret=False),
                [sds((nb, B), jnp.float32)])
    if name == "bucket_scatter":
        return (lambda l, v: bucket_scatter_pallas(l, v, B, interpret=False),
                [sds((nb, K), jnp.int32), sds((nb, K), jnp.float32)])
    if name == "qsgd_pack":
        return (lambda x, r: qsgd_pack_pallas(x, r, BITS, "l2",
                                              interpret=False),
                [sds((nbq, BQ), jnp.float32), sds((nbq, BQ), jnp.uint32)])
    return (lambda p, s: qsgd_unpack_pallas(p, s, BITS, interpret=False),
            [sds((nbq, W), jnp.uint32), sds((nbq, 1), jnp.float32)])


@pytest.mark.parametrize("rows", sorted(ROWS))
@pytest.mark.parametrize("name", ["bucket_topk", "bucket_scatter",
                                  "qsgd_pack", "qsgd_unpack"])
def test_kernel_compiles_for_v5e(one_chip, name, rows):
    nb, nbq = ROWS[rows]
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    fn, args = _kernel_call(name, nb, nbq, sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
