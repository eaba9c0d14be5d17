#!/usr/bin/env python3
"""Chip smoke: serve a SIFT1M-shaped collection on a TPU through the
store's own entry points, and check the answers on the chip.

    python chip_smoke.py             # one chip: the fused 'inline' kernel path
    python chip_smoke.py --chips 4   # four chips: the sharded placement only

A smoke run, not a benchmark: it proves that the main path compiles and
answers correctly on the device.  The wall times it prints come from one
unrepeated run and are for orientation only.

Shape: ANN-Benchmarks ``sift-128-euclidean`` (n = 1,000,000, d = 128,
L2, k = 10), generated from ``--seed`` with ``repro.data.make_clustered``
and ``normalize_scale`` as ``benchmarks/search_hotpath.py`` does; 256
queries; nothing is downloaded.  Index: c = 1.5, t = 64, K = 10, L = 5.

One chip: ``Collection`` in the inline layout, served by
``StoreService(interpret=False)`` across two tenants.  Checked against
the jnp engine (id-set parity), an exact float64 recomputation of every
returned distance, and recall@10 against HIGHEST-precision brute force.

Four chips: ``ShardedCollection`` over a 4-device mesh at 4M x 128 (1M
rows per chip), served by ``StoreService``; then 4,096 rows are added,
1,024 served ids removed, the collection compacted once (the all_to_all
migration) and served again.  Checked: a shard resident on every device,
recall@10 against an exact search of the surviving rows, and no removed
row ever returned.

Exits non-zero, printing no result line, when jax finds no TPU.  The
last stdout line is the verdict:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from importlib import metadata
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

D, K_NN, N_QUERIES = 128, 10, 256
INDEX_KW = dict(c=1.5, t=64, k=K_NN, K=10, L=5)
BATCH_SHAPES = (1, 8, 32)
TENANTS = ("web", "batch")
PARITY_MIN, RECALL_MIN, DIST_RTOL = 0.99, 0.25, 1e-3
N_ADD, N_REMOVE = 4096, 1024
FAR = 1e15  # masks a removed row out of the exact reference search


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def gib(nbytes) -> str:
    return "not reported" if nbytes is None else f"{nbytes / 2**30:.3f} GiB"


def mem_stat(dev, key: str):
    stats = dev.memory_stats()
    return None if not stats else stats.get(key)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"smoke check failed: {what}")
    say(f"check ok: {what}")


def generate(seed: int, n: int, n_queries: int, n_extra: int = 0, rows=None):
    """One ``make_clustered`` draw split into data (n, D), extra rows to
    insert later, and queries, scaled by ``normalize_scale``.  ``rows``
    (a NamedSharding) places the data row-sharded as it is drawn."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.data import make_clustered, normalize_scale

    kd, kb = jax.random.split(jax.random.key(seed))

    def draw(key):
        pts = make_clustered(key, n + n_extra + n_queries, D,
                             n_clusters=250, spread=0.02)
        return pts[:n], pts[n:n + n_extra], pts[n + n_extra:]

    out = None
    if rows is not None:
        rep = NamedSharding(rows.mesh, P())
        out = (rows, rep, rep)
    data, extra, queries = jax.jit(draw, out_shardings=out)(kd)
    data, queries, scale = normalize_scale(data, queries)
    return data, extra * scale, queries, kb


def serve_all(svc, name: str, queries: np.ndarray):
    """Every query through ``StoreService.serve`` (which raises a
    ticket's error), half per tenant; returns (dists, ids, tickets)."""
    half = queries.shape[0] // 2
    parts = [svc.serve(name, queries[:half], tenant=TENANTS[0]),
             svc.serve(name, queries[half:], tenant=TENANTS[1])]
    tickets = parts[0][2] + parts[1][2]
    check(len(tickets) == queries.shape[0]
          and all(t.done and t.error is None and not t.degraded
                  for t in tickets),
          f"{len(tickets)}/{queries.shape[0]} queries served, none errored "
          "or degraded")
    return (np.concatenate([parts[0][0], parts[1][0]]),
            np.concatenate([parts[0][1], parts[1][1]]), tickets)


def recall_at_k(got: np.ndarray, truth: np.ndarray) -> float:
    k = truth.shape[1]
    return float(np.mean([len(set(a.tolist()) & set(b.tolist())) / k
                          for a, b in zip(got, truth)]))


def check_distances(dists, vectors, queries) -> None:
    """Every returned distance against a float64 recomputation."""
    exact = np.sqrt(np.sum(
        np.square(vectors.astype(np.float64)
                  - queries.astype(np.float64)[:, None, :]), axis=-1))
    err = np.abs(dists - exact) / np.maximum(exact, 1e-12)
    check(np.all(np.isfinite(dists)) and float(err.max()) <= DIST_RTOL,
          f"all {dists.size} returned distances within rtol {DIST_RTOL} of "
          f"an exact recomputation (max rel err {float(err.max()):.3g})")


# ------------------------------------------------------------------ 1 chip
def single_chip(*, n: int, n_queries: int, seed: int,
                interpret: bool = False) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import brute_force, search_batch_fixed
    from repro.store import Collection, StoreService

    dev = jax.devices()[0]
    t0 = time.perf_counter()
    data, _, queries, kb = generate(seed, n, n_queries)
    jax.block_until_ready(data)
    say(f"data: {n} x {D} float32 + {n_queries} queries, generated in "
        f"{time.perf_counter() - t0:.2f} s")

    t0 = time.perf_counter()
    col = Collection.create("sift1m", kb, data, inline_vectors=True,
                            engine="inline", **INDEX_KW)
    jax.block_until_ready(col.index)
    idx = col.index
    logical = idx.memory_bytes() + idx.data.nbytes
    say(f"build: {time.perf_counter() - t0:.2f} s (compile included); "
        f"K={idx.params.K} L={idx.params.L} M={idx.params.max_blocks} "
        f"B={idx.params.block_size}; index + data logical {gib(logical)}, "
        f"device bytes_in_use {gib(mem_stat(dev, 'bytes_in_use'))}")

    svc = StoreService(batch_shapes=BATCH_SHAPES, default_k=K_NN,
                       engine="inline", interpret=interpret)
    svc.attach(col)
    q_host = np.asarray(queries)
    shapes = svc.batch_shapes
    lowered = search_batch_fixed.lower(
        idx, jnp.asarray(q_host[:shapes[-1]]), k=K_NN, r0=svc.r0,
        steps=svc.steps, engine="inline", interpret=interpret,
        with_stats=True,
    ).as_text()
    if not interpret:
        check("tpu_custom_call" in lowered,
              "the inline engine lowers to a compiled TPU kernel "
              "(tpu_custom_call), not the Pallas interpreter")
    for shape in shapes:  # the service's exact dispatch: compile + 1 run
        t0 = time.perf_counter()
        jax.block_until_ready(col.search(
            q_host[:shape], k=K_NN, r0=svc.r0, steps=svc.steps,
            engine="inline", with_stats=True, interpret=interpret))
        say(f"compile + first call, inline engine, batch {shape}: "
            f"{time.perf_counter() - t0:.2f} s")

    t0 = time.perf_counter()
    dists, ids, _ = serve_all(svc, "sift1m", q_host)
    say(f"serve: {n_queries} queries in {time.perf_counter() - t0:.3f} s "
        f"wall through StoreService (batch shapes {shapes}, tenants "
        f"{TENANTS})")

    ref_ids = np.concatenate([
        np.asarray(col.search(q_host[i:i + shapes[-1]], k=K_NN, r0=svc.r0,
                              steps=svc.steps, engine="jnp")[1])
        for i in range(0, n_queries, shapes[-1])
    ])
    parity = float(np.mean([set(a.tolist()) == set(b.tolist())
                            for a, b in zip(ids, ref_ids)]))
    check(parity >= PARITY_MIN,
          f"inline-vs-jnp id-set parity {parity:.4f} >= {PARITY_MIN}")

    check(bool(np.all(ids < n)), "every result slot filled")
    vecs = np.asarray(jnp.take(data, jnp.asarray(ids.reshape(-1)), axis=0))
    check_distances(dists, vecs.reshape(ids.shape + (D,)), q_host)

    _, gt = brute_force(data, queries, k=K_NN)
    rec = recall_at_k(ids, np.asarray(gt))
    check(rec >= RECALL_MIN,
          f"recall@{K_NN} {rec:.4f} >= {RECALL_MIN} against brute force")
    say(f"device peak bytes_in_use {gib(mem_stat(dev, 'peak_bytes_in_use'))}")


# ----------------------------------------------------------------- 4 chips
def exact_knn_sharded(data, dead, queries, mesh, k: int, extra=None):
    """Exact k-NN rows of ``queries`` over the live rows of row-sharded
    ``data`` and, numbered after them, the rows of ``extra``:
    ``brute_force`` on each shard (``dead`` rows moved out of reach), one
    all_gather, a host-side merge."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map
    from repro.core import brute_force

    n_local = data.shape[0] // mesh.shape["data"]

    def local(x, gone, q):
        d, i = brute_force(jnp.where(gone[:, None], FAR, x), q, k=k)
        i = i + jax.lax.axis_index("data") * n_local
        return (jax.lax.all_gather(d, "data", axis=1, tiled=True),
                jax.lax.all_gather(i, "data", axis=1, tiled=True))

    d, i = jax.jit(shard_map(local, mesh=mesh,
                             in_specs=(P("data"), P("data"), P()),
                             out_specs=(P(), P())))(data, dead, queries)
    d, i = [np.asarray(d)], [np.asarray(i)]
    if extra is not None:
        de, ie = brute_force(extra, queries, k=k)
        d.append(np.asarray(de))
        i.append(np.asarray(ie) + data.shape[0])
    d, i = np.concatenate(d, axis=1), np.concatenate(i, axis=1)
    return np.take_along_axis(i, np.argsort(d, axis=1)[:, :k], axis=1)


def four_chips(*, n_per_chip: int, n_queries: int, seed: int, devices,
               n_add: int = N_ADD, n_remove: int = N_REMOVE) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.compat import make_mesh
    from repro.store import ShardedCollection, StoreService

    mesh = make_mesh((4,), ("data",), devices=devices[:4])
    n = 4 * n_per_chip
    t0 = time.perf_counter()
    data, extra, queries, kb = generate(
        seed, n, n_queries, n_add, rows=NamedSharding(mesh, P("data")))
    jax.block_until_ready(data)
    say(f"data: {n} x {D} float32 row-sharded over 4 devices + {n_add} "
        f"rows to add + {n_queries} queries, generated in "
        f"{time.perf_counter() - t0:.2f} s")

    t0 = time.perf_counter()
    col = ShardedCollection.create(
        "sift4m", kb, data, mesh, payload=np.arange(n, dtype=np.int32),
        **INDEX_KW)
    jax.block_until_ready(col.sharded.index)
    s = col.sharded
    say(f"build: {time.perf_counter() - t0:.2f} s (compile included); "
        f"n_local={s.n_local} stride={s.stride} K={s.index.params.K} "
        f"L={s.index.params.L}")
    per_dev = [mem_stat(d, "bytes_in_use") for d in devices[:4]]
    shard_bytes = n_per_chip * D * 4
    say("bytes_in_use per device: " + ", ".join(gib(b) for b in per_dev))
    holders = {sh.device for sh in s.index.data.addressable_shards
               if sh.data.shape[0] == s.n_local}
    check(len(holders) == 4
          and all(b is None or b >= shard_bytes for b in per_dev),
          f"a {gib(shard_bytes)} data shard resident on each of the 4 devices")

    svc = StoreService(batch_shapes=BATCH_SHAPES, default_k=K_NN,
                       interpret=False)
    svc.attach(col)
    q_host = np.asarray(queries)
    t0 = time.perf_counter()
    _, ids0, tickets = serve_all(svc, "sift4m", q_host)
    say(f"serve (compile included): {n_queries} queries in "
        f"{time.perf_counter() - t0:.2f} s wall")
    rows0 = np.stack([t.payload for t in tickets])
    gt0 = exact_knn_sharded(data, jnp.zeros((n,), bool), queries, mesh, K_NN)
    say(f"recall@{K_NN} before mutation: {recall_at_k(rows0, gt0):.4f}")

    # remove ids the service actually returned, so "never returned again"
    # is a real test; then grow and migrate
    gone_ids = np.unique(ids0[ids0 < col.id_space])[:n_remove]
    gone_rows = np.asarray(col.get_payload(jnp.asarray(gone_ids)))
    check(gone_ids.size == n_remove, f"{n_remove} served ids to remove")
    t0 = time.perf_counter()
    col.add(extra, payload=np.arange(n, n + n_add, dtype=np.int32))
    col.remove(gone_ids)
    col.compact()
    jax.block_until_ready(col.sharded.index)
    s = col.sharded
    say(f"add {n_add} + remove {n_remove} + compact: "
        f"{time.perf_counter() - t0:.2f} s (compile included); live "
        f"{col.live_count()}, n_local {s.n_local}")
    check(col.live_count() == n + n_add - n_remove, "live count after churn")

    t0 = time.perf_counter()
    dists, _, tickets = serve_all(svc, "sift4m", q_host)
    say(f"serve after compact (compile included): {n_queries} queries in "
        f"{time.perf_counter() - t0:.2f} s wall")
    rows = np.stack([t.payload for t in tickets])
    check(not np.isin(rows, gone_rows).any(), "no removed row returned")

    dead = np.zeros(n, bool)
    dead[gone_rows[gone_rows < n]] = True
    gt = exact_knn_sharded(
        data, jax.device_put(dead, NamedSharding(mesh, P("data"))), queries,
        mesh, K_NN, extra=extra)
    rec = recall_at_k(rows, gt)
    check(rec >= RECALL_MIN,
          f"recall@{K_NN} {rec:.4f} >= {RECALL_MIN} against an exact search "
          "of the surviving rows")
    everything = jnp.concatenate([data, extra])
    vecs = np.asarray(jnp.take(everything, jnp.asarray(rows.reshape(-1)),
                               axis=0))
    check_distances(dists, vecs.reshape(rows.shape + (D,)), q_host)
    say("peak bytes_in_use per device: " + ", ".join(
        gib(mem_stat(d, "peak_bytes_in_use")) for d in devices[:4]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the sharded phase alone, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {devices[0].platform}); "
              "this script runs on the chip only", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    import jaxlib

    from repro.jit_cache import enable_compile_cache

    dev = devices[0]
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}; jax {jax.__version__}, jaxlib "
        f"{jaxlib.__version__}, libtpu {metadata.version('libtpu')}")
    say(f"compile cache: {enable_compile_cache()}")
    say("a smoke run, not a benchmark: times are one unrepeated run")
    t0 = time.perf_counter()
    if args.chips == 1:
        single_chip(n=1_000_000, n_queries=N_QUERIES, seed=args.seed)
    else:
        four_chips(n_per_chip=1_000_000, n_queries=N_QUERIES,
                   seed=args.seed, devices=devices)
    say(f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
