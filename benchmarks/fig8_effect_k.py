"""Fig. 8 reproduction: recall / overall ratio as k varies."""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import brute_force
from repro.jit_cache import enable_compile_cache

from .common import load_dataset, methods_for, recall_and_ratio, timed


def run(ks=(1, 10, 25, 50, 100), dataset="deep-s", scale=0.5):
    data, queries = load_dataset(dataset, scale)
    Q = jnp.asarray(queries)
    rows = []
    for k in ks:
        gt = brute_force(jnp.asarray(data), Q, k=k)
        for method, (search, _) in methods_for(data, k=k).items():
            (d, i), ms = timed(search, Q, k=k, repeats=2)
            rec, ratio = recall_and_ratio(d, i, gt[0], gt[1], k)
            rows.append({"k": k, "method": method, "recall": rec,
                         "ratio": ratio, "query_ms_per_q": ms / Q.shape[0]})
    return rows


def main(ks=(1, 10, 50)):
    rows = run(ks)
    print(f"{'k':>5}{'method':<14}{'recall':>8}{'ratio':>8}{'q_ms':>8}")
    for r in rows:
        print(f"{r['k']:>5}{r['method']:<14}{r['recall']:>8.3f}"
              f"{r['ratio']:>8.3f}{r['query_ms_per_q']:>8.2f}")
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    main()
