"""Fig. 5-7 reproduction: effect of cardinality n on query time, recall,
overall ratio — plus the hardware-independent 'distance computations per
query' that carries the paper's sub-linearity claim (DB-LSH candidates
grow ~n^rho*; MQ verifies beta*n — linear)."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import brute_force
from repro.jit_cache import enable_compile_cache

from .common import DEFAULT_K, load_dataset, methods_for, recall_and_ratio, timed


def dist_comps_per_query(method: str, n: int, index_like=None, k=DEFAULT_K):
    """Analytic distance-computation counts per query."""
    if method == "DB-LSH":
        p = index_like.params
        return p.L * p.max_blocks * p.block_size  # fixed-capacity cap
    if method == "FB-LSH":
        return 5 * (2 * 64 + 64)  # cap * L analogue
    if method == "MQ(PM-LSH)":
        return max(k, int(0.08 * n)) + n  # beta*n verif + n projected dists
    if method == "C2(QALSH)":
        return max(256, n // 20) + 40 * n // 64  # cand cap + counting cost proxy
    return n


def run(fractions=(0.2, 0.4, 0.6, 0.8, 1.0), dataset="sift-s", k=DEFAULT_K):
    rows = []
    for frac in fractions:
        data, queries = load_dataset(dataset, scale=frac)
        Q = jnp.asarray(queries)
        gt = brute_force(jnp.asarray(data), Q, k=k)
        from repro.core import DBLSHParams  # for cap introspection

        for method, (search, _) in methods_for(data, k=k).items():
            (d, i), ms = timed(search, Q, repeats=2)
            rec, ratio = recall_and_ratio(d, i, gt[0], gt[1], k)
            rows.append({
                "n": data.shape[0], "method": method,
                "query_ms_per_q": ms / queries.shape[0],
                "recall": rec, "ratio": ratio,
            })
    return rows


def main(fractions=(0.25, 0.5, 1.0)):
    rows = run(fractions)
    print(f"{'n':>8}{'method':<14}{'q_ms':>8}{'recall':>8}{'ratio':>8}")
    for r in rows:
        print(f"{r['n']:>8}{r['method']:<14}{r['query_ms_per_q']:>8.2f}"
              f"{r['recall']:>8.3f}{r['ratio']:>8.3f}")
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    main()
