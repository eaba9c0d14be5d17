"""Table IV reproduction: query time / overall ratio / recall / indexing
time for DB-LSH vs FB-LSH vs MQ vs C2 on the scaled datasets.

Paper claims to validate (Table IV + §VI-B):
  * DB-LSH beats FB-LSH on recall AND query time (query-centric buckets);
  * DB-LSH has the smallest indexing time;
  * DB-LSH reaches the best recall/ratio at the lowest query time.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import brute_force
from repro.jit_cache import enable_compile_cache

from .common import DEFAULT_K, SCALED_DATASETS, load_dataset, methods_for, recall_and_ratio, timed


def run(scale: float = 1.0, datasets=None, k: int = DEFAULT_K):
    rows = []
    for name in datasets or SCALED_DATASETS:
        data, queries = load_dataset(name, scale)
        Q = jnp.asarray(queries)
        gt_d, gt_i = brute_force(jnp.asarray(data), Q, k=k)
        for method, (search, idx_time) in methods_for(data, k=k).items():
            (d, i), ms = timed(search, Q)
            rec, ratio = recall_and_ratio(d, i, gt_d, gt_i, k)
            rows.append({
                "dataset": name, "method": method,
                "query_ms_per_q": ms / queries.shape[0],
                "recall": rec, "overall_ratio": ratio,
                "index_s": idx_time,
            })
    return rows


def main(scale=0.5):
    rows = run(scale)
    hdr = f"{'dataset':<10}{'method':<12}{'q_ms':>8}{'recall':>8}{'ratio':>8}{'idx_s':>8}"
    print(hdr)
    for r in rows:
        print(f"{r['dataset']:<10}{r['method']:<12}{r['query_ms_per_q']:>8.2f}"
              f"{r['recall']:>8.3f}{r['overall_ratio']:>8.3f}{r['index_s']:>8.2f}")
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    main()
