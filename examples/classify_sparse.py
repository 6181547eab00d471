"""Large-scale sparse classification (paper §8.2 / Table 2, the MPI-OPT
scenario): logistic regression over a URL-like trigram-sparse dataset,
one data-parallel rank per device, exploiting NATURAL gradient sparsity
losslessly.

    PYTHONPATH=src python examples/classify_sparse.py
    # on the CPU, with 8 emulated ranks:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        PYTHONPATH=src python examples/classify_sparse.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.allreduce import make_sparse_allreduce
from repro.data.sparse_datasets import make_url_like_dataset
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    n_feat = 1 << 20
    idx, val, y = make_url_like_dataset(n_samples=2048, n_features=n_feat,
                                        nnz_per_sample=64)
    ranks = len(jax.devices())
    mesh = jax.make_mesh((ranks,), ("data",))
    print(f"dataset: 2048 samples x {n_feat} trigram features "
          f"(density {64/n_feat:.5%}) — gradients are naturally sparse")

    w = np.zeros(n_feat, np.float32)
    lr, bs = 0.5, 16  # per-rank batch

    def rank_grad(w, rank, step):
        lo = (step * ranks + rank) * bs % 2048
        ii, vv, yy = idx[lo:lo + bs], val[lo:lo + bs], y[lo:lo + bs]
        m = (vv * w[ii]).sum(1)
        coef = (-yy / (1 + np.exp(yy * m)) / bs).astype(np.float32)
        g = np.zeros(n_feat, np.float32)
        np.add.at(g, ii.ravel(), (coef[:, None] * vv).ravel())
        return g

    def accuracy(w):
        m = (val * w[idx]).sum(1)
        return float((np.sign(m) == y).mean())

    for algo in ("dense", "ssar_split_allgather"):
        f = make_sparse_allreduce(mesh, "data", n_feat, k_per_bucket=8,
                                  bucket_size=512, algorithm=algo)
        w = np.zeros(n_feat, np.float32)
        t0 = time.perf_counter()
        for step in range(16):
            grads = np.stack([rank_grad(w, r, step) for r in range(ranks)])
            summed = np.asarray(f(jnp.asarray(grads).reshape(-1), None))
            w -= lr * summed / ranks
        dt = time.perf_counter() - t0
        print(f"  {algo:22s}: 16 steps in {dt:.2f}s, "
              f"train accuracy {accuracy(w):.3f}")


if __name__ == "__main__":
    main()
