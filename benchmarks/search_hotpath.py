"""Serving hot-path benchmark: fused one-pass pipeline vs the multi-pass seed.

Measures ``search_batch_fixed`` (one-pass incremental probing; the
Pallas engines run the fully fused select->gather->verify->bin->merge
kernel) against ``search_batch_fixed_ref`` (the per-radius re-selection
seed algorithm) on a synthetic reference workload and emits
``BENCH_search_hotpath.json`` — the repo's BENCH trajectory point for
the serving search core.

Row schema (one row per engine x dtype):

* ``engine`` / ``dtype`` / ``mode`` — ``mode`` records how the row was
  executed: ``compiled`` (XLA) or ``interpret`` (Pallas interpreter on a
  non-TPU host).  Interpret rows price every in-kernel op at
  Python-dispatch cost; they validate semantics and relative slot-work,
  not absolute device throughput.  Re-measuring on a real accelerator
  replaces the ``interpret`` rows with ``compiled`` ones under the same
  schema (ROADMAP BENCH carry-over).
* ``qps_ref`` / ``qps_new`` / ``speedup`` — seed vs one-pass wall QPS at
  the SAME ``n_queries`` (every engine measures the full batch).
* ``passes`` / ``slot_work_qps`` — the fused kernels execute
  ``1 + steps`` pipeline passes per verified slot in-kernel (distance +
  the per-step bin merges the unfused path ran as separate XLA programs
  over an HBM pool); ``slot_work_qps = qps_new * passes`` is the
  interpret-mode-normalized throughput comparable against the historical
  dist-only kernel row (1 pass).
* ``recall_ref`` / ``recall_new`` — recall@k vs brute force.
* ``parity`` — fraction of queries whose one-pass id set equals the
  multi-pass seed's.  Not exactly 1.0 by design: under block-budget
  truncation the one-pass path keeps the M best blocks of the *final*
  window rather than re-ranking per step (DESIGN.md §7), so a handful
  of queries legitimately differ (gated >= 0.95 for fp32 rows).
* ``engine_parity`` — fraction of queries whose id set equals the jnp
  row's at the same dtype: same pipeline, different engine.  This is
  the exact gate (== 1.0 for fp32 rows) pinning the fused kernels
  against the pool path at full workload scale.  Quantized rows report
  it but are gated on the recall band instead — the shortlist is
  approximate by contract.

Gates (exit 1): slot accounting (one-pass never verifies more slots than
the seed, with per-step decay), fp32 engine parity == 1.0 and seed
parity >= 0.95, recall parity ±0.5pt, quantized recall within 0.5pt of
fp32, jnp speedup >= 1.5x, and — full mode — fused-kernel slot-work
>= 2x the historical dist-only kernel row.

Full mode (default): n=100k, d=64, steps=8, all engines at n_queries=64.
Smoke mode (``--smoke``): tiny n, seconds on CPU (the CI gate).
``--large``: n=1M jnp-only point (minutes on CPU).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    brute_force,
    build,
    DBLSHParams,
    search_batch_fixed,
    search_batch_fixed_ref,
)
from repro.core.serve_search import _select_blocks
from repro.data import make_clustered, normalize_scale
from repro.jit_cache import enable_compile_cache

try:  # module run (benchmarks.run) vs script run (python benchmarks/...)
    from .common import recall_at, timed
except ImportError:
    from common import recall_at, timed

#: the dist-only Pallas kernel row of the pre-fusion BENCH (qps_new of
#: engine=kernel in the last committed BENCH_search_hotpath.json before
#: the fused kernel landed): 1 in-kernel pass per slot, merges in XLA.
OLD_KERNEL_DIST_ONLY_QPS = 20.44


def per_step_slots(index, Q, r0: float, steps: int):
    """Hardware-level verified-slot counts per schedule step.

    seed: every selected (blk < nb) slot of every step's fresh selection;
    new:  only the slots of blocks newly admitted at that step (the
    final-radius selection masked on block halfwidths).  Both count the
    full compiled program's gather work (no done-masking), which is what
    the device actually executes."""
    p = index.params
    nb = index.nb
    B = p.block_size
    G = jnp.einsum("lkd,qd->qlk", index.proj_vecs, jnp.asarray(Q))

    seed_counts, new_counts = [], []
    r = jnp.asarray(r0, jnp.float32)
    r_last = jnp.asarray(r0, jnp.float32)
    for _ in range(steps - 1):
        r_last = r_last * p.c
    _, bhw = _select_blocks(index, G, p.w0 * r_last)
    prev_half = -np.inf
    for _ in range(steps):
        half = 0.5 * (p.w0 * r)
        blk_j, _ = _select_blocks(index, G, p.w0 * r)
        seed_counts.append(int(jnp.sum(blk_j < nb)) * B)
        newly = (bhw <= half) & (bhw > prev_half)
        new_counts.append(int(jnp.sum(newly)) * B)
        prev_half = half
        r = r * p.c
    return seed_counts, new_counts


def _parity_frac(d_ref, i_ref, d_new, i_new):
    """Fraction of queries whose finite id set matches the seed's."""
    d_ref, i_ref, d_new, i_new = map(np.asarray, (d_ref, i_ref, d_new, i_new))
    hits = 0
    for q in range(d_ref.shape[0]):
        fr, fn = np.isfinite(d_ref[q]), np.isfinite(d_new[q])
        hits += set(i_ref[q][fr]) == set(i_new[q][fn])
    return hits / max(1, d_ref.shape[0])


def run(
    n: int = 100_000,
    d: int = 64,
    n_queries: int = 64,
    steps: int = 8,
    k: int = 10,
    r0: float = 0.5,
    rows: tuple[tuple[str, str], ...] = (("jnp", "fp32"),),
    repeats: int = 3,
    smoke: bool = False,
    seed: int = 7,
) -> dict:
    key = jax.random.key(seed)
    kd, kb = jax.random.split(key)
    allpts = make_clustered(kd, n + n_queries, d,
                            n_clusters=max(8, n // 4000), spread=0.02)
    data, queries = allpts[:n], allpts[n:]
    data, queries, _ = normalize_scale(data, queries)
    inline = any(e == "inline" for e, _ in rows)
    dtypes = {dt for _, dt in rows}
    # one index serves fp32 + one quantized dtype; a second build covers
    # the other quantized dtype (same data, same LSH key -> same layout)
    main_q = "int8" if "int8" in dtypes else (
        "bf16" if "bf16" in dtypes else "none")
    base_kw = dict(n=n, d=d, c=1.5, t=64, k=max(k, 10), K=10, L=5,
                   inline_vectors=inline)
    params = DBLSHParams.derive(quant_dtype=main_q, **base_kw)
    t0 = time.perf_counter()
    index = build(kb, jnp.asarray(data), params)
    jax.block_until_ready(index.proj_blocks)
    build_s = time.perf_counter() - t0
    indexes = {"fp32": index, main_q: index}
    for dt in dtypes - set(indexes):
        p2 = DBLSHParams.derive(quant_dtype=dt, **base_kw)
        indexes[dt] = build(kb, jnp.asarray(data), p2)

    _, gt_i = brute_force(jnp.asarray(data), jnp.asarray(queries), k=k)

    interp_host = jax.default_backend() != "tpu"
    report = {
        "bench": "search_hotpath",
        "smoke": smoke,
        "notes": (
            "Every row measures the full n_queries batch. 'interpret' "
            "rows run the Pallas interpreter on a non-TPU host: each "
            "in-kernel op costs a Python dispatch, so wall QPS tracks "
            "op count, not device throughput — slot_work_qps (qps x "
            "in-kernel passes per slot) is the comparable number. "
            "Re-measure on a TPU to replace interpret rows with "
            "compiled ones (same schema)."
        ),
        "workload": {
            "n": n, "d": d, "n_queries": n_queries, "steps": steps,
            "k": k, "r0": r0, "K": params.K, "L": params.L,
            "max_blocks": params.max_blocks, "block_size": params.block_size,
            "build_s": round(build_s, 3),
        },
        "old_kernel_dist_only_qps": OLD_KERNEL_DIST_ONLY_QPS,
        "rows": [],
    }

    Q = jnp.asarray(queries)
    ref_cache: dict[str, tuple] = {}
    base_cache: dict[str, tuple] = {}
    for engine, dtype in rows:
        idx = indexes[dtype if dtype != "fp32" else "fp32"]
        mode = "interpret" if (engine != "jnp" and interp_host) else "compiled"
        rep = repeats if mode == "compiled" else 1

        if engine not in ref_cache:
            (d_ref, i_ref), ms_ref = timed(
                lambda: search_batch_fixed_ref(
                    index, Q, k=k, r0=r0, steps=steps, engine=engine
                ),
                repeats=max(1, rep),
            )
            ref_cache[engine] = (d_ref, i_ref, ms_ref)
        d_ref, i_ref, ms_ref = ref_cache[engine]

        (d_new, i_new), ms_new = timed(
            lambda: search_batch_fixed(
                idx, Q, k=k, r0=r0, steps=steps, engine=engine, dtype=dtype
            ),
            repeats=max(1, rep),
        )
        rec_ref = recall_at(i_ref, gt_i, k)
        rec_new = recall_at(i_new, gt_i, k)
        # fused engines run 1 distance pass + `steps` bin-merge folds per
        # slot in-kernel; jnp and the seed keep merges outside the kernel
        fused = engine in ("kernel", "inline")
        passes = (1 + steps) if fused else 1
        qps_new = n_queries * 1e3 / ms_new
        # engine parity: same one-pass pipeline, different engine — the
        # jnp row at the same dtype is the baseline.  This is the gate
        # that pins the fused kernels against the pool path at full
        # workload scale; parity-vs-ref below additionally carries the
        # (documented, §7) one-pass-vs-multi-pass truncation delta.
        if engine == "jnp":
            base_cache[dtype] = (d_new, i_new)
            engine_parity = 1.0
        elif dtype in base_cache:
            bd, bi = base_cache[dtype]
            engine_parity = _parity_frac(bd, bi, d_new, i_new)
        else:
            engine_parity = None
        report["rows"].append({
            "engine": engine,
            "dtype": dtype,
            "mode": mode,
            "n_queries": n_queries,
            "qps_ref": round(n_queries * 1e3 / ms_ref, 2),
            "qps_new": round(qps_new, 2),
            "speedup": round(ms_ref / ms_new, 3),
            "passes": passes,
            "slot_work_qps": round(qps_new * passes, 2),
            "recall_ref": round(rec_ref, 4),
            "recall_new": round(rec_new, 4),
            "parity": round(_parity_frac(d_ref, i_ref, d_new, i_new), 4),
            "engine_parity": (None if engine_parity is None
                              else round(engine_parity, 4)),
        })

    seed_steps, new_steps = per_step_slots(
        index, queries[: min(n_queries, 32)], r0, steps
    )
    report["per_step_slots"] = {"ref": seed_steps, "new": new_steps}
    report["slot_check"] = {
        "total_ref": int(sum(seed_steps)),
        "total_new": int(sum(new_steps)),
        "ok": sum(new_steps) <= sum(seed_steps),
    }
    return report


def _gates(report) -> bool:
    ok = True
    sc = report["slot_check"]
    if not sc["ok"]:
        print(f"FAIL: one-pass verified {sc['total_new']} slots > seed "
              f"{sc['total_ref']}", file=sys.stderr)
        ok = False
    # per-step decay gate: after step 0 the one-pass path only verifies
    # fresh-block deltas, so each step must sit below the seed's full
    # re-selection
    ref_steps = report["per_step_slots"]["ref"]
    new_steps = report["per_step_slots"]["new"]
    for j, (rj, nj) in enumerate(zip(ref_steps, new_steps)):
        bad = nj > rj if j == 0 else (rj > 0 and nj >= rj)
        if bad:
            print(f"FAIL: step {j} one-pass verified {nj} slots vs seed "
                  f"{rj} (no per-step decay)", file=sys.stderr)
            ok = False
    fp32_recall = {r["engine"]: r["recall_new"]
                   for r in report["rows"] if r["dtype"] == "fp32"}
    for r in report["rows"]:
        tag = f"{r['engine']}/{r['dtype']}"
        if abs(r["recall_new"] - r["recall_ref"]) > 0.005 + 1e-9:
            print(f"FAIL: {tag} recall drift {r['recall_ref']} -> "
                  f"{r['recall_new']} exceeds 0.5pt", file=sys.stderr)
            ok = False
        if r["dtype"] == "fp32":
            # fused engines must match the jnp one-pass path exactly —
            # same distances, same merge semantics, different engine
            ep = r.get("engine_parity")
            if ep is not None and ep < 1.0 - 1e-9:
                print(f"FAIL: {tag} fused-vs-jnp engine parity "
                      f"{ep} < 1.0", file=sys.stderr)
                ok = False
            # vs the multi-pass seed the one-pass path keeps the M best
            # blocks of the *final* window rather than re-ranking per
            # step (DESIGN.md §7) — under truncation a handful of
            # queries legitimately differ, so this band is loose where
            # the engine-parity gate above is exact
            if r["parity"] < 0.95 - 1e-9:
                print(f"FAIL: {tag} one-pass-vs-seed id-set parity "
                      f"{r['parity']} < 0.95", file=sys.stderr)
                ok = False
        else:
            base = fp32_recall.get(r["engine"])
            if base is not None and base - r["recall_new"] > 0.005 + 1e-9:
                print(f"FAIL: {tag} quantized recall {r['recall_new']} "
                      f"more than 0.5pt below fp32 {base}", file=sys.stderr)
                ok = False
    jnp_rows = [r for r in report["rows"]
                if r["engine"] == "jnp" and r["dtype"] == "fp32"]
    if not report["smoke"] and jnp_rows and jnp_rows[0]["speedup"] < 1.5:
        print("FAIL: jnp speedup below 1.5x", file=sys.stderr)
        ok = False
    if not report["smoke"]:
        for r in report["rows"]:
            if r["engine"] == "kernel" and r["dtype"] == "fp32":
                floor = 2.0 * report["old_kernel_dist_only_qps"]
                if r["slot_work_qps"] < floor:
                    print(f"FAIL: fused kernel slot-work {r['slot_work_qps']}"
                          f" qps < 2x dist-only baseline ({floor})",
                          file=sys.stderr)
                    ok = False
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload (CI gate)")
    ap.add_argument("--large", action="store_true",
                    help="n=1M jnp-only point")
    ap.add_argument("--out", default="BENCH_search_hotpath.json")
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--engines", default=None,
                    help="comma-separated subset of jnp,kernel,inline")
    args = ap.parse_args(argv)

    if args.smoke:
        engines = ("jnp", "kernel")
        dt_rows = (("jnp", "int8"),)
        kw = dict(n=args.n or 4096, d=24, n_queries=16, repeats=1, smoke=True)
    elif args.large:
        engines = ("jnp",)
        dt_rows = (("jnp", "int8"),)
        kw = dict(n=args.n or 1_000_000, n_queries=64)
    else:
        engines = ("jnp", "kernel", "inline")
        dt_rows = (("jnp", "int8"), ("jnp", "bf16"), ("kernel", "int8"))
        kw = dict(n=args.n or 100_000, n_queries=64)
    if args.engines:
        engines = tuple(args.engines.split(","))
        dt_rows = tuple((e, dt) for e, dt in dt_rows if e in engines)
    rows = tuple((e, "fp32") for e in engines) + dt_rows

    report = run(rows=rows, **kw)

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    for r in report["rows"]:
        print(f"search_hotpath/{r['engine']}/{r['dtype']} [{r['mode']}]: "
              f"ref {r['qps_ref']} qps -> new {r['qps_new']} qps "
              f"({r['speedup']}x, slot-work {r['slot_work_qps']}), recall "
              f"{r['recall_ref']} -> {r['recall_new']}, parity {r['parity']}"
              f", engine-parity {r['engine_parity']}")
    print("per-step slots ref:", report["per_step_slots"]["ref"])
    print("per-step slots new:", report["per_step_slots"]["new"])

    ok = _gates(report)
    sc = report["slot_check"]
    print("gates:", "OK" if ok else "FAILED",
          f"(new {sc['total_new']} <= ref {sc['total_ref']})")
    return 0 if ok else 1


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
