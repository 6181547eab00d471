"""Quickstart: train a small LM with SparCML gradient compression.

    PYTHONPATH=src python examples/quickstart.py

Data parallel over every device present (on the CPU, export
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu for
8 emulated ones), comparing dense allreduce vs the paper's Quantized TopK
SGD (Alg. 2: bucketed top-k + error feedback + DSAR split/allgather +
4-bit QSGD second phase).
"""
import jax
import jax.numpy as jnp

from repro.core.compressor import SyncConfig, wire_bytes_per_step
from repro.data.pipeline import DataConfig, synthetic_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.config import ModelConfig
from repro.models.model import build_model
from repro.optim.optimizers import OptimizerConfig
from repro.optim.schedule import ScheduleConfig
from repro.train.state import TrainConfig
from repro.train.train_step import build_train_step, init_state


def main():
    enable_compile_cache()
    mesh = make_host_mesh()
    cfg = ModelConfig(name="quickstart-12m", family="dense", num_layers=4,
                      d_model=256, num_heads=8, num_kv_heads=4, d_ff=512,
                      vocab_size=2048, dtype=jnp.float32,
                      param_dtype=jnp.float32, max_seq_len=256)
    model = build_model(cfg)
    data = DataConfig(global_batch=16, seq_len=128, vocab_size=2048)

    for label, sync in [
        ("dense allreduce      ", SyncConfig(mode="dense")),
        ("sparcml topk 1.6%+EF ", SyncConfig(
            mode="sparcml", k_per_bucket=8, bucket_size=512,
            algorithm="dsar_split_allgather", qsgd_bits=4,
            min_sparse_size=16384, impl="ref")),
    ]:
        tcfg = TrainConfig(sync=sync, optimizer=OptimizerConfig(),
                           schedule=ScheduleConfig(peak_lr=1e-3,
                                                   warmup_steps=10,
                                                   total_steps=500))
        step_fn, (shapes, _) = build_train_step(model, tcfg, mesh)
        state, _ = init_state(model, tcfg, mesh)
        key = jax.random.PRNGKey(0)
        with mesh:
            for i in range(40):
                batch = jax.tree.map(jnp.asarray, synthetic_batch(data, i))
                state, m = step_fn(state, batch, jax.random.fold_in(key, i))
                if i % 10 == 0:
                    print(f"  [{label}] step {i:3d} loss {float(m['loss']):.4f}")
        print(f"  [{label}] final loss {float(m['loss']):.4f}")
        p = mesh.shape["data"]
        if p > 1:   # one rank sends nothing
            rep = wire_bytes_per_step(shapes.params, sync, p=p)
            print(f"  [{label}] wire bytes/step: "
                  f"{rep['sparcml_bytes']/1e6:.2f} MB "
                  f"({rep['ratio']:.1f}x less than dense)")
        print()


if __name__ == "__main__":
    main()
