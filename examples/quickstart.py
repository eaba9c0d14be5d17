"""Quickstart: build a DB-LSH index and run (c,k)-ANN queries.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import DBLSHParams, brute_force, build, search_batch, search_batch_fixed
from repro.data import make_clustered, normalize_scale
from repro.jit_cache import enable_compile_cache


def main():
    key = jax.random.key(0)
    n, d, k = 20_000, 64, 10

    # dataset + queries (queries drawn from the data distribution)
    pts = make_clustered(key, n + 100, d, n_clusters=32, spread=0.02)
    data, queries = pts[:n], pts[n:]
    data, queries, _ = normalize_scale(data, queries)  # NN distance ~ 1 (paper WLOG)

    # paper parameters: c=1.5, w0=4c^2; K/L derived from (n, t)
    params = DBLSHParams.derive(n=n, d=d, c=1.5, t=64, k=k, K=10, L=5)
    print(f"K={params.K} L={params.L} rho*={params.rho:.4f} "
          f"alpha={params.alpha():.3f} budget={params.budget}")

    index = build(jax.random.key(1), data, params)
    print(f"index: {index.nb} blocks/table x {params.L} tables, "
          f"{index.memory_bytes() / 2**20:.1f} MiB")

    # paper-faithful adaptive search (Algorithm 2)
    dists, ids = search_batch(index, queries, k=k, r0=0.5)
    # TPU serving path (fixed schedule)
    dists_f, ids_f = search_batch_fixed(index, queries, k=k, r0=0.5, steps=6)

    gt_d, gt_i = brute_force(data, queries, k=k)
    for name, I in [("adaptive", ids), ("fixed", ids_f)]:
        rec = np.mean([len(set(np.asarray(a)) & set(np.asarray(b))) / k
                       for a, b in zip(I, gt_i)])
        print(f"{name:<9} recall@{k} = {rec:.3f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
