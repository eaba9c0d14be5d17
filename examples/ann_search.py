"""ANN serving through the vector store: build a Collection, stream
single queries from two tenants through the StoreService scheduler
(overlapped dispatch + query-result cache + per-tenant quotas), mutate
the collection online (add/remove -> auto-compaction, which invalidates
the cache by version), and report recall + scheduler stats.  The final
section runs the *same* mutable lifecycle on a ShardedCollection (one
shard per visible device) — add/remove/compact at fleet scale through
the identical service path.

    PYTHONPATH=src:. python examples/ann_search.py [--scale 0.25]

For the paper-table benchmark (DB-LSH vs competitor families) run
``python benchmarks/table4_query_perf.py``; for sustained-QPS curves run
``python benchmarks/store_throughput.py``.
"""

import argparse
import json

import jax
import numpy as np

from benchmarks.common import load_dataset, recall_and_ratio
from repro.compat import make_mesh
from repro.core import brute_force
from repro.jit_cache import enable_compile_cache
from repro.store import (
    Collection,
    CompactionPolicy,
    QuotaExceeded,
    ShardedCollection,
    StoreService,
)


def main(scale: float = 0.25, dataset: str = "sift-s"):
    data, queries = load_dataset(dataset, scale=scale)
    n_hold = data.shape[0] // 4  # held back for the online-update phase
    base, extra = data[:-n_hold], data[-n_hold:]
    k = 10

    print(f"[build] {dataset} scale={scale}: n={base.shape[0]} d={base.shape[1]}")
    col = Collection.create(
        "demo",
        jax.random.key(1),
        base,
        c=1.5,
        t=64,
        k=k,
        policy=CompactionPolicy(growth_ratio=1.25),
        payload=np.arange(base.shape[0]),  # payload demo: row ids
        engine="jnp",  # per-collection default; submit/serve may override
    )
    svc = StoreService(
        batch_shapes=(1, 8, 32), default_k=k, r0=0.5, steps=8,
        inflight_depth=2,  # overlap: pad batch i+1 while the device runs i
    )
    svc.attach(col)
    # two tenants share the queue: 'web' gets 3x the batch share, 'batch'
    # is capped to a small token bucket (over-quota submits are rejected)
    svc.set_quota("web", weight=3)
    svc.set_quota("batch", rate=50.0, burst=8, weight=1)

    # --- serve a stream of single queries through the admission queue ----
    dists, ids, _ = svc.serve("demo", queries, k=k, tenant="web")
    gt_d, gt_i = brute_force(base, queries, k=k)
    rec, ratio = recall_and_ratio(dists, ids, gt_d, gt_i, k)
    print(f"[serve] recall@{k}={rec:.3f} ratio={ratio:.3f}")

    # repeats hit the query-result cache (no device dispatch at all)
    dists_c, ids_c, reqs_c = svc.serve("demo", queries, k=k, tenant="web")
    assert all(r.cached for r in reqs_c) and np.array_equal(ids_c, ids)
    rejected = 0
    for q in queries:
        try:
            svc.submit("demo", q, k=k, tenant="batch")
        except QuotaExceeded:
            rejected += 1
    svc.flush()
    print(f"[tenants] {json.dumps(svc.tenant_stats(), indent=2)}")
    print(f"[stats] {json.dumps(svc.stats('demo'), indent=2)}")
    print(f"[cache] {svc.cache_stats()} rejected={rejected}")

    # --- recall-target planning: calibrate once, then ask for outcomes ---
    # (repro.tune: the planner picks (r0, steps) off the table and C1/C2
    # adaptive termination stops easy queries before the planned budget)
    col.calibrate(queries[: min(32, len(queries))], k=k)
    t = svc.submit("demo", queries[0], k=k, tenant="web", recall_target=0.9)
    svc.flush()
    hist = svc.stats("demo")["termination_steps_hist"]
    print(f"[tune] recall_target=0.9 -> planned steps={t.plan.steps} "
          f"(r0={t.plan.r0:.3f}), took {t.radius_steps} steps; "
          f"termination histogram {hist}")

    # --- EXPLAIN ANALYZE one query: the full per-query story -------------
    # (repro.obs.explain: plan provenance, cache/queue placement, the
    # per-step half-windows + admitted slots the device measured, and
    # which termination condition fired.  Explain'd requests batch
    # separately and bypass the cache read, so results stay bit-equal
    # to a plain submit of the same query.)
    te = svc.submit("demo", queries[0], k=k, tenant="web", explain=True)
    svc.flush()
    assert np.array_equal(te.ids, ids_c[0])  # same answer, now explained
    print("[explain]")
    print(te.explain.render())

    # --- online growth: adds cross the policy threshold -> auto-compact ---
    # (every mutation bumps col.version, so cached results can't go stale)
    v0 = col.version
    col.add(extra, payload=np.arange(base.shape[0], data.shape[0]))
    print(f"[update] n={col.n} compactions={col.stats.compactions} "
          f"version {v0} -> {col.version}")
    dists, ids, reqs = svc.serve("demo", queries, k=k, tenant="web")
    assert not any(r.cached for r in reqs)  # old entries unreachable
    gt_d, gt_i = brute_force(data, queries, k=k)
    rec2, _ = recall_and_ratio(dists, ids, gt_d, gt_i, k)
    print(f"[serve] post-growth recall@{k}={rec2:.3f}")

    # --- the same lifecycle at fleet scale: ShardedCollection ------------
    # one shard per visible device (1 on a CPU host — the protocol is
    # identical at any P); the service serves it through the same queue,
    # cache, and policy path as the local collection above.
    pn = len(jax.devices())
    mesh = make_mesh((pn,), ("data",))
    n_shard = (base.shape[0] // pn) * pn
    sc = ShardedCollection.create(
        "demo-sharded", jax.random.key(2), base[:n_shard], mesh,
        c=1.5, t=64, k=k, payload=np.arange(n_shard),
        policy=CompactionPolicy(auto=False),
    )
    svc.attach(sc)
    _, _, reqs_s = svc.serve("demo-sharded", queries, k=k, tenant="web")
    sv0 = sc.version
    sc.add(extra[:64], payload=np.arange(n_shard, n_shard + 64))
    # ids are stable under sharded adds (strided id space, DESIGN.md
    # §9): search results and add() handles stay valid until the next
    # compact(), whose id map reports the one renumbering event
    d_f, i_f = sc.search(queries[:4], k=k, r0=0.5, steps=8)
    sc.remove(np.unique(np.asarray(i_f)[np.isfinite(np.asarray(d_f))])[:16])
    sc.compact()
    print(f"[sharded x{pn}] live={sc.live_count()} "
          f"shard_counts={sc.shard_counts().tolist()} "
          f"compactions={sc.stats.compactions} version {sv0} -> {sc.version}")
    _, _, reqs_s2 = svc.serve("demo-sharded", queries, k=k, tenant="web")
    assert not any(r.cached for r in reqs_s2)  # mutations invalidated


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.25)
    ap.add_argument("--dataset", default="sift-s")
    args = ap.parse_args()
    main(scale=args.scale, dataset=args.dataset)
