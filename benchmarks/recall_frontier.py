"""Recall-vs-QPS frontier: planned adaptive termination vs fixed schedule.

The fixed serving schedule forces one (r0, steps) on every query: easy
queries pay the full probe budget, hard queries stop wherever the
schedule ends.  The ``repro.tune`` subsystem replaces that with a
calibrated plan (r0 anchored to the collection's NN-distance scale) and
per-query C1/C2 termination.  This benchmark pins the trade as a BENCH
trajectory (``BENCH_recall_frontier.json``):

* the **fixed frontier** — recall@k, QPS, and mean verified slots for
  every schedule length ``1..steps`` at the calibrated r0;
* the **adaptive point** — the same budget ``steps`` with
  ``Termination()`` (C1 candidate budget + C2 certification + batch
  early exit): its recall with its mean termination step and mean
  verified slots, which must beat the fixed schedule's at equal recall;
* the **planner's answer** — the schedule ``RecallTarget`` picks off
  the calibration table for a sweep of targets.

Gates (exit 1 on failure; CI runs ``--smoke`` on every push):
  * adaptive recall within 1pt of the fixed schedule at the same length
    (equal recall band) with mean termination step strictly below it;
  * adaptive mean verified slots ≤ fixed (strict in full mode — the
    acceptance point: recall@10 ≥ 0.85 at n=100k, d=64 with strictly
    fewer verified slots than the fixed 8-step schedule).

Full mode: n=100k, d=64.  Smoke (``--smoke``): tiny n, CPU-seconds.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import numpy as np
import jax.numpy as jnp

from repro.core import DBLSHParams, Termination, brute_force, build, search_batch_fixed
from repro.data import make_clustered, normalize_scale
from repro.jit_cache import enable_compile_cache
from repro.tune import (
    RecallTarget,
    calibrate,
    plan,
    search_batch_adaptive,
    termination_step_histogram,
)

try:  # module run (benchmarks.run) vs script run (python benchmarks/...)
    from .common import recall_at, timed
except ImportError:
    from common import recall_at, timed


def run(
    n: int = 100_000,
    d: int = 64,
    n_queries: int = 64,
    n_calib: int = 32,
    steps: int = 8,
    k: int = 10,
    engine: str = "jnp",
    repeats: int = 3,
    smoke: bool = False,
    seed: int = 7,
) -> dict:
    key = jax.random.key(seed)
    kd, kb = jax.random.split(key)
    allpts = make_clustered(kd, n + n_queries + n_calib, d,
                            n_clusters=max(8, n // 4000), spread=0.02)
    data = allpts[:n]
    queries = allpts[n:n + n_queries]
    calib_q = allpts[n + n_queries:]
    data, queries, scale = normalize_scale(data, queries)
    calib_q = calib_q * scale
    # max_blocks above the derived floor: at n=100k the 2(2t+k)/B budget
    # gives M=5, and five MINDIST-best blocks per table are all admitted
    # by the first window — truncation, not the radius schedule, would
    # govern admission and the frontier would be flat.  M=16 keeps the
    # schedule the binding constraint (per-step admission actually grows
    # with the radius), which is the regime the planner exists for.
    params = DBLSHParams.derive(
        n=n, d=d, c=1.5, t=64, k=max(k, 10), K=10, L=5, max_blocks=16,
    )
    t0 = time.perf_counter()
    index = build(kb, jnp.asarray(data), params)
    jax.block_until_ready(index.proj_blocks)
    build_s = time.perf_counter() - t0

    # calibrate on the held-out sample: r0 comes off the data's
    # NN-distance scale, per-length recall/cost back the planner
    table = calibrate(index, jnp.asarray(calib_q), k=k, steps_max=steps,
                      engine=engine)
    r0 = table.r0

    _, gt_i = brute_force(jnp.asarray(data), jnp.asarray(queries), k=k)
    Q = jnp.asarray(queries)

    report = {
        "bench": "recall_frontier",
        "smoke": smoke,
        "workload": {
            "n": n, "d": d, "n_queries": n_queries, "n_calib": n_calib,
            "steps": steps, "k": k, "engine": engine,
            "K": params.K, "L": params.L, "max_blocks": params.max_blocks,
            "block_size": params.block_size, "c1_budget": params.budget,
            "r0_calibrated": round(float(r0), 6),
            "build_s": round(build_s, 3),
        },
        "calibration": {
            "recall": [round(x, 4) for x in table.recall],
            "cost_slots": [round(x, 1) for x in table.cost_slots],
        },
    }

    # ---- fixed frontier: one point per schedule length
    fixed = []
    for j in range(1, steps + 1):
        (dd, ii, ss), ms = timed(
            lambda j=j: search_batch_fixed(
                index, Q, k=k, r0=r0, steps=j, engine=engine,
                with_stats=True,
            ),
            repeats=max(1, repeats),
        )
        fixed.append({
            "steps": j,
            "recall": round(recall_at(ii, gt_i, k), 4),
            "qps": round(n_queries * 1e3 / ms, 2),
            "mean_slots": round(float(np.asarray(ss["candidates"]).mean()), 1),
            "mean_term_step": round(
                float(np.asarray(ss["radius_steps"]).mean()), 3),
        })
    report["fixed"] = fixed

    # ---- adaptive point: same budget, C1+C2 done masks + early exit
    term = Termination()
    (da, ia, sa), ms_a = timed(
        lambda: search_batch_adaptive(
            index, Q, k=k, r0=r0, steps=steps, engine=engine,
            termination=term,
        ),
        repeats=max(1, repeats),
    )
    hist = termination_step_histogram(sa, steps)
    report["adaptive"] = {
        "steps_budget": steps,
        "recall": round(recall_at(ia, gt_i, k), 4),
        "qps": round(n_queries * 1e3 / ms_a, 2),
        "mean_slots": round(float(np.asarray(sa["candidates"]).mean()), 1),
        "mean_term_step": round(
            float(np.asarray(sa["radius_steps"]).mean()), 3),
        "term_step_hist": [int(x) for x in hist],
    }

    # ---- what the planner answers for a sweep of recall targets
    report["planner"] = [
        {"target": t_, "steps": plan(table, RecallTarget(t_)).steps}
        for t_ in (0.5, 0.8, 0.85, 0.9, 0.95)
    ]

    # ---- the planned adaptive point: RecallTarget(0.85) end to end —
    # the planner picks the schedule off the calibration table, adaptive
    # termination trims easy queries inside it.  This is the acceptance
    # point: recall@k >= 0.85 with strictly fewer verified slots than
    # the full fixed schedule.
    planned = plan(table, RecallTarget(0.85, max_steps=steps))
    (dp, ip, sp), ms_p = timed(
        lambda: search_batch_adaptive(
            index, Q, k=k, r0=planned.r0, steps=planned.steps,
            engine=engine, termination=planned.termination,
        ),
        repeats=max(1, repeats),
    )
    report["planned_adaptive"] = {
        "target": 0.85,
        "steps_planned": planned.steps,
        "recall": round(recall_at(ip, gt_i, k), 4),
        "qps": round(n_queries * 1e3 / ms_p, 2),
        "mean_slots": round(float(np.asarray(sp["candidates"]).mean()), 1),
        "mean_term_step": round(
            float(np.asarray(sp["radius_steps"]).mean()), 3),
        "term_step_hist": [
            int(x) for x in termination_step_histogram(sp, planned.steps)
        ],
    }
    return report


def _gate(report: dict) -> bool:
    ok = True
    fixed_last = report["fixed"][-1]
    ad = report["adaptive"]
    steps = fixed_last["steps"]

    # equal recall band: the adaptive path may trade at most 1pt of the
    # full fixed schedule's recall for its saved work
    if ad["recall"] < fixed_last["recall"] - 0.01 - 1e-9:
        print(f"FAIL: adaptive recall {ad['recall']} more than 1pt below "
              f"fixed {fixed_last['recall']}", file=sys.stderr)
        ok = False
    # ...and inside that band it must actually save schedule steps
    if not ad["mean_term_step"] < steps:
        print(f"FAIL: adaptive mean termination step {ad['mean_term_step']} "
              f"not strictly below the fixed {steps}-step schedule",
              file=sys.stderr)
        ok = False
    if ad["mean_slots"] > fixed_last["mean_slots"] + 1e-9:
        print(f"FAIL: adaptive verified {ad['mean_slots']} mean slots > "
              f"fixed {fixed_last['mean_slots']}", file=sys.stderr)
        ok = False
    pa = report["planned_adaptive"]
    if pa["mean_term_step"] >= steps:
        print(f"FAIL: planned-adaptive mean termination step "
              f"{pa['mean_term_step']} not below the fixed {steps}-step "
              "schedule", file=sys.stderr)
        ok = False
    if not report["smoke"]:
        # the acceptance point: recall floor with strict slot savings
        if pa["recall"] < 0.85:
            print(f"FAIL: planned-adaptive recall {pa['recall']} below the "
                  "0.85 acceptance floor", file=sys.stderr)
            ok = False
        if not pa["mean_slots"] < fixed_last["mean_slots"]:
            print(f"FAIL: planned-adaptive mean slots {pa['mean_slots']} not "
                  f"strictly below fixed {fixed_last['mean_slots']}",
                  file=sys.stderr)
            ok = False
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload (CI gate)")
    ap.add_argument("--out", default="BENCH_recall_frontier.json")
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--engine", default="jnp")
    args = ap.parse_args(argv)

    if args.smoke:
        report = run(n=args.n or 8192, d=24, n_queries=32, n_calib=16,
                     repeats=1, engine=args.engine, smoke=True)
    else:
        report = run(n=args.n or 100_000, engine=args.engine)

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")

    for row in report["fixed"]:
        print(f"fixed/steps={row['steps']}: recall {row['recall']}, "
              f"{row['qps']} qps, {row['mean_slots']} slots")
    ad = report["adaptive"]
    print(f"adaptive/budget={ad['steps_budget']}: recall {ad['recall']}, "
          f"{ad['qps']} qps, {ad['mean_slots']} slots, mean term step "
          f"{ad['mean_term_step']}, hist {ad['term_step_hist']}")
    print("planner:", ", ".join(
        f"recall>={p['target']}→{p['steps']} steps" for p in report["planner"]
    ))
    pa = report["planned_adaptive"]
    print(f"planned-adaptive/target=0.85: {pa['steps_planned']} steps, "
          f"recall {pa['recall']}, {pa['qps']} qps, {pa['mean_slots']} "
          f"slots, mean term step {pa['mean_term_step']}")

    ok = _gate(report)
    print("frontier gates:", "OK" if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
