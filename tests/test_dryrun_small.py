"""Miniature dry-run: lower+compile the production code path on an 8-device
mesh for representative archs (full 16x16/2x16x16 runs live in
launch/dryrun.py; this keeps the invariant under pytest)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as cfgreg
from repro.configs._common import make_train_config
from repro.launch.mesh import make_host_mesh
from repro.models.model import build_model
from repro.train.train_step import build_train_step, state_shapes


def small_mesh(multi_pod=False):
    if multi_pod:
        return make_host_mesh(data=2, model=2, pod=2)
    return make_host_mesh(data=4, model=2)


@pytest.mark.parametrize("multi_pod", [False, True])
@pytest.mark.parametrize("sync", ["dense", "sparcml"])
def test_train_cell_lowers_and_compiles(multi_pod, sync):
    mesh = small_mesh(multi_pod)
    cfg = cfgreg.smoke_config("qwen3-4b")
    model = build_model(cfg)
    tcfg = make_train_config(sync_mode=sync, fsdp=(sync == "dense"))
    with mesh:
        step_fn, (shapes, _) = build_train_step(model, tcfg, mesh)
        b = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        lowered = step_fn.lower(shapes, b, key)
        compiled = lowered.compile()
        assert compiled.cost_analysis() is not None
        # the paper's collectives must appear in sparcml mode (native
        # collectives in the manual-dp region — DESIGN.md §4)
        hlo = compiled.as_text()
        if sync == "sparcml":
            assert "all-to-all" in hlo, "DSAR split phase missing"
            assert "all-gather" in hlo, "DSAR gather phase missing"


@pytest.mark.parametrize("arch", ["mamba2-370m", "zamba2-2.7b", "dbrx-132b"])
def test_decode_cell_lowers(arch):
    mesh = small_mesh()
    from repro.serve.engine import build_serve_step
    cfg = cfgreg.smoke_config(arch)
    model = build_model(cfg)
    with mesh:
        dec_fn, _ = build_serve_step(model, mesh, batch_size=8, cache_len=64)
        pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        st = jax.eval_shape(lambda: model.init_decode_state(8, 64, prefix_len=63))
        toks = jax.ShapeDtypeStruct((8, 1), jnp.int32)
        dec_fn.lower(pshapes, st, toks).compile()


def test_input_specs_are_abstract():
    from repro.launch.dryrun import input_specs
    spec = input_specs("qwen3-4b", "train_4k")
    assert spec["tokens"].shape == (256, 4096)
    assert spec["tokens"].dtype == jnp.int32
    spec2 = input_specs("hubert-xlarge", "prefill_32k")
    assert spec2["frames"].shape == (32, 32768, 512)
    spec3 = input_specs("llama-3.2-vision-11b", "train_4k")
    assert spec3["image_embeds"].shape == (256, 1600, 1280)
