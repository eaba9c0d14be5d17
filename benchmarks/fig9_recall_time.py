"""Fig. 9/10 reproduction: recall-time / ratio-time trade-off curves.

The paper sweeps the approximation ratio c; here we sweep the DB-LSH
radius-schedule length (steps) and c, which spans the same trade-off —
fewer probes = faster + less accurate."""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import brute_force, search_batch_fixed
from repro.jit_cache import enable_compile_cache

from .common import DEFAULT_K, build_dblsh, load_dataset, recall_and_ratio, timed


def run(dataset="deep-s", scale=0.5, k=DEFAULT_K):
    data, queries = load_dataset(dataset, scale)
    Q = jnp.asarray(queries)
    gt = brute_force(jnp.asarray(data), Q, k=k)
    rows = []
    for c in (2.0, 1.5, 1.2):
        index, _ = build_dblsh(data, c=c, k=k)
        for steps in (2, 4, 6, 8, 10):
            (d, i), ms = timed(
                lambda Q: search_batch_fixed(index, Q, k=k, r0=0.5, steps=steps), Q,
                repeats=2,
            )
            rec, ratio = recall_and_ratio(d, i, gt[0], gt[1], k)
            rows.append({"c": c, "steps": steps, "recall": rec, "ratio": ratio,
                         "query_ms_per_q": ms / Q.shape[0]})
    return rows


def main():
    rows = run()
    print(f"{'c':>5}{'steps':>6}{'q_ms':>8}{'recall':>8}{'ratio':>8}")
    for r in rows:
        print(f"{r['c']:>5.1f}{r['steps']:>6}{r['query_ms_per_q']:>8.2f}"
              f"{r['recall']:>8.3f}{r['ratio']:>8.3f}")
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    main()
