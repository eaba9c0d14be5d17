"""Sustained-QPS benchmark for the store scheduler.

Streams single queries through the StoreService admission queue at each
(engine, batch-size) point in three modes — synchronous dispatch
(``inflight_depth=0``), overlapped dispatch (the in-flight ring), and
overlapped + query-result cache on a repeat-heavy stream — plus one
multi-tenant point with a quota-limited tenant.  Emits a JSON report
with per-point ``overlap_ratio`` / ``cache_hit_rate`` and per-tenant
QPS.

``--obs`` benchmarks the observability contract instead: the same
overlapped stream with ``repro.obs`` fully enabled (tracing, sample
rate 1.0) vs disabled vs EXPLAIN-sampled (per-query explain records at
the recommended 1/64 rate), interleaved best-of-rounds.  It asserts
bit-equal results across all three arms, writes the metrics registry
(JSON + Prometheus text), the trace (JSONL + Perfetto timeline), and
the sampled explains (JSON) as artifacts, verifies the timeline shows
the in-flight ring overlap, and — with ``--gate`` — hard-fails if the
tracing-enabled or explain-sampled overhead exceeds ``--max-overhead``
(default 5%).

``--sharded-updates`` benchmarks the *mutable sharded lifecycle*
instead: a ShardedCollection absorbs interleaved add / remove / compact
ops while serving queries through the StoreService, reporting mutation
throughput (points/s added and removed, compaction wall time) alongside
query QPS before and after the churn.  With ``--smoke`` the run doubles
as a correctness gate: it asserts post-churn recall against a brute
force of the surviving point set and that deleted points never
resurface (non-zero exit on violation) — the CI hook for the sharded
lifecycle.

``--chaos`` soaks the resilience layer instead: a scripted fault matrix
(transient + persistent dispatch raises, injected latency spikes under
the brownout ladder, snapshot-writer kills at every crash stage) with
hard gates — no ticket lost or hung, non-flagged results bit-equal the
fault-free reference, degraded-phase p99 within 2x the healthy
baseline, brownout heals to level 0, every snapshot crash recovers a
verified committed state.  ``--smoke`` shrinks it to CI size; the JSON
report is the chaos-soak artifact.

Caveat for CPU-only hosts: the "device" shares cores with the host, so
overlapped dispatch has nothing to hide behind and lands within noise
of sync (~0.95-1.05x) — the overlap win needs a real accelerator,
where issue returns while the TPU/GPU runs the batch.  The cache mode
is host-independent and shows its full gain everywhere.

    PYTHONPATH=src python benchmarks/store_throughput.py \
        [--scale 0.2] [--batch-sizes 8 32] [--engines jnp] \
        [--sharded-updates] [--smoke] [--out store_throughput.json]

CPU-friendly at the default scale; on an accelerator raise --scale and
add the Pallas engines (kernel / inline) to the sweep (the sharded mode
fans out over every device the host exposes).
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

try:
    # python -m benchmarks.store_throughput
    from .common import load_dataset, recall_and_ratio
except ImportError:
    # python benchmarks/store_throughput.py
    from common import load_dataset, recall_and_ratio

from repro.compat import make_mesh
from repro.core import brute_force
from repro.jit_cache import enable_compile_cache
from repro.obs import DEFAULT_EXPLAIN_SAMPLE_RATE, Observability, Tracer
from repro.obs.metrics import MetricsRegistry
from repro.store import (
    Collection,
    CompactionPolicy,
    QuotaExceeded,
    ShardedCollection,
    StoreService,
)


def _make_service(col, *, batch_size: int, engine: str, k: int, r0: float,
                  steps: int, inflight_depth: int, cache_size: int):
    svc = StoreService(
        batch_shapes=(batch_size,), max_wait_ms=1e9, default_k=k,
        r0=r0, steps=steps, engine=engine, inflight_depth=inflight_depth,
        cache_size=cache_size,
    )
    svc.attach(col)
    return svc


def _stream(svc, col_name, stream, batch_size):
    # depth 0 completes each batch inside step() (synchronous); depth > 0
    # leaves the ring full and only flush() syncs the tail.
    t0 = time.perf_counter()
    for q in stream:
        svc.submit(col_name, q)
        if svc.pending() >= batch_size:
            svc.step()
    svc.flush()
    return time.perf_counter() - t0


def _bench_modes(col, queries, *, batch_size: int, engine: str, k: int,
                 n_queries: int, r0: float, steps: int,
                 rounds: int = 3) -> dict:
    """All three modes at one (engine, batch-size) point.

    ``sync``/``overlapped`` measure dispatch on an all-unique stream
    (cache off — the tiled stream repeats queries, and serving repeats
    from the cache would measure the wrong thing); ``cached`` measures a
    repeat-heavy stream with the cache on.  The modes are measured
    *interleaved* round-robin and each keeps its best round: machine
    speed drifts on shared hosts, and interleaving keeps the drift from
    loading onto whichever mode happened to run last.
    """
    reps = -(-n_queries // queries.shape[0])
    tiled = np.tile(queries, (reps, 1))[:n_queries]
    # all-unique stream: perturb each row so no two are bit-equal
    jitter = 1e-4 * np.arange(n_queries, dtype=np.float32)[:, None]
    distinct = (tiled + jitter).astype(np.float32)
    # repeat-heavy stream for the cache point: few uniques, many repeats
    n_unique = max(1, min(queries.shape[0], n_queries // 4))
    repeats = np.tile(queries[:n_unique], (-(-n_queries // n_unique), 1))
    repeats = repeats[:n_queries].astype(np.float32)

    # depth 2 = the two-stage pipeline (pad batch i+1 while the device
    # runs batch i); much deeper rings contend on CPU.
    modes = {
        "sync": (distinct, 0, 0),
        "overlapped": (distinct, 2, 0),
        "cached": (repeats, 2, 4 * n_queries),
    }

    def run(mode):
        stream, depth, cache_size = modes[mode]
        svc = _make_service(
            col, batch_size=batch_size, engine=engine, k=k, r0=r0,
            steps=steps, inflight_depth=depth, cache_size=cache_size,
        )
        wall = _stream(svc, col.name, stream, batch_size)
        return svc, wall

    best: dict[str, tuple] = {}
    for mode in modes:
        run(mode)  # warmup: compiles the (batch_size, d) program
    for _ in range(rounds):
        for mode in modes:
            svc, wall = run(mode)
            if mode not in best or wall < best[mode][1]:
                best[mode] = (svc, wall)

    out = {}
    for mode, (svc, wall) in best.items():
        stats = svc.stats(col.name)
        out[mode] = {
            "mode": mode,
            "engine": engine,
            "batch_size": batch_size,
            "inflight_depth": modes[mode][1],
            "queries": n_queries,
            "wall_s": wall,
            "sustained_qps": n_queries / wall,
            "latency_ms_p50": stats["latency_ms_p50"],
            "latency_ms_p99": stats["latency_ms_p99"],
            "mean_radius_steps": stats["mean_radius_steps"],
            "mean_candidates": stats["mean_candidates"],
            "batches": stats["batches"],
            "overlap_ratio": stats["overlap_ratio"],
            "cache_hit_rate": stats["cache_hit_rate"],
        }
    return out


def _bench_tenants(col, queries, *, batch_size: int, engine: str, k: int,
                   n_queries: int, r0: float, steps: int) -> dict:
    """Two tenants share the queue: 'bulk' is unlimited, 'capped' has a
    small token bucket.  Reports per-tenant QPS / rejects and shows WRR
    draining keeps serving both."""
    svc = _make_service(
        col, batch_size=batch_size, engine=engine, k=k, r0=r0, steps=steps,
        inflight_depth=4, cache_size=0,
    )
    svc.set_quota("bulk", weight=3)
    svc.set_quota("capped", rate=200.0, burst=16, weight=1)
    reps = -(-n_queries // queries.shape[0])
    stream = np.tile(queries, (reps, 1))[:n_queries]
    rejected = 0
    t0 = time.perf_counter()
    for i, q in enumerate(stream):
        tenant = "capped" if i % 4 == 0 else "bulk"
        try:
            svc.submit(col.name, q, tenant=tenant)
        except QuotaExceeded:
            rejected += 1
        if svc.pending() >= batch_size:
            svc.step()
    svc.flush()
    wall = time.perf_counter() - t0
    return {
        "batch_size": batch_size,
        "engine": engine,
        "wall_s": wall,
        "rejected": rejected,
        "per_tenant": svc.tenant_stats(),
    }


def _overlap_visible(tracer: Tracer) -> bool:
    """True when the trace shows ring overlap *structurally*: some
    batch's issue span sits inside an earlier batch's in-flight window
    (its issue's end to its completion's start, by ``seq``), on a
    different ring lane — the picture a Perfetto load should show."""
    issues = [s for s in tracer.events if s.name == "batch.issue"]
    completes = {s.args["seq"]: s for s in tracer.events
                 if s.name == "batch.complete"}
    for p in issues:
        c = completes.get(p.args["seq"])
        if c is None:
            continue
        for i in issues:
            if (
                i.args["seq"] > p.args["seq"]
                and i.tid != p.tid
                and p.ts + p.dur <= i.ts
                and i.ts + i.dur <= c.ts
            ):
                return True
    return False


def bench_obs(
    scale: float = 0.2,
    dataset: str = "sift-s",
    batch_size: int = 16,
    engine: str = "jnp",
    k: int = 10,
    n_queries: int = 128,
    rounds: int = 5,
    max_overhead: float = 0.05,
    gate: bool = False,
    out: str = "store_obs.json",
):
    """Observability overhead + artifact benchmark (the repro.obs gate).

    Runs the same all-unique overlapped stream three times per round —
    obs off (metrics only, tracing disabled), obs fully on (tracing
    enabled, sample_rate 1.0), and EXPLAIN-sampled (auto-explain at
    :data:`DEFAULT_EXPLAIN_SAMPLE_RATE`, which splits sampled requests
    into their own ``with_explain`` batches) — interleaved, keeping each
    arm's best round (shared hosts drift; interleaving keeps the drift
    off one arm).  Asserts all arms return **bit-equal** results, writes
    the enabled arm's metrics registry (JSON + Prometheus text), trace
    (JSONL + Perfetto ``trace_event`` timeline), and the explain arm's
    sampled-explains JSON next to ``out``, and verifies the timeline
    actually shows ring overlap (batch N+1's issue span between batch N's
    issue and completion, one lane up).  With ``gate`` the ≤ ``max_overhead``
    overhead contract is a hard assert on the tracing *and* explain
    arms — the CI hook.
    """
    data, queries = load_dataset(dataset, scale=scale)
    col = Collection.create(
        "bench", jax.random.key(1), data, c=1.5, t=64, k=k,
        payload=np.arange(data.shape[0]),
    )
    reps = -(-n_queries // queries.shape[0])
    tiled = np.tile(queries, (reps, 1))[:n_queries]
    jitter = 1e-4 * np.arange(n_queries, dtype=np.float32)[:, None]
    stream = (tiled + jitter).astype(np.float32)

    def run(traced: bool, explain_rate: float = 0.0):
        # private tracer per run: the global one must stay untouched so
        # the obs-off arm is genuinely off
        obs = Observability(
            registry=MetricsRegistry(),
            tracer=Tracer(enabled=False),
            trace=traced,
            explain_sample_rate=explain_rate,
        )
        # the singleton shape is what keeps explain sampling cheap: a
        # sampled request batches separately (different compiled
        # program), and without a (1,) rung it would pad out to a full
        # batch_size dispatch — ~30% overhead instead of ~3% at 1/64
        svc = StoreService(
            batch_shapes=(1, batch_size), max_wait_ms=1e9, default_k=k,
            r0=0.5, steps=8, engine=engine, inflight_depth=2,
            cache_size=0, obs=obs,
        )
        svc.attach(col)
        tickets = []
        t0 = time.perf_counter()
        for q in stream:
            tickets.append(svc.submit("bench", q))
            if svc.pending() >= batch_size:
                svc.step()
        svc.flush()
        wall = time.perf_counter() - t0
        d = np.stack([t.dists for t in tickets])
        i = np.stack([t.ids for t in tickets])
        return svc, obs, wall, d, i

    # three arms: obs off, obs fully on (tracing), and explain sampling
    # at the recommended production rate (splits sampled requests into
    # their own with_explain batches — the cost under test)
    ARMS = {
        "off": lambda: run(False),
        "on": lambda: run(True),
        "explain": lambda: run(False,
                               explain_rate=DEFAULT_EXPLAIN_SAMPLE_RATE),
    }
    for arm in ARMS.values():  # warmup: compiles both dispatch programs
        arm()
    best = {}
    for _ in range(rounds):
        for key, arm in ARMS.items():
            svc, obs, wall, d, i = arm()
            if key not in best or wall < best[key][2]:
                best[key] = (svc, obs, wall, d, i)

    _, _, wall_off, d_off, i_off = best["off"]
    svc_on, obs_on, wall_on, d_on, i_on = best["on"]
    _, obs_ex, wall_ex, d_ex, i_ex = best["explain"]

    # contract 1: observability never changes results
    assert np.array_equal(d_off, d_on) and np.array_equal(i_off, i_on), (
        "obs-enabled results diverged from obs-off"
    )
    # contract 1b: sampled EXPLAIN never changes results either — the
    # explain'd requests run a separate compiled program but must land
    # bit-equal where the plain dispatch would have put them
    assert np.array_equal(d_off, d_ex) and np.array_equal(i_off, i_ex), (
        "explain-sampled results diverged from explain-off"
    )
    overhead = wall_on / wall_off - 1.0
    overhead_ex = wall_ex / wall_off - 1.0

    # contract 2: the exported timeline shows the ring overlap
    overlap_ok = _overlap_visible(obs_on.tracer)
    stats = svc_on.stats("bench")
    if stats["overlap_ratio"] > 0:
        assert overlap_ok, (
            "overlapped batches ran but the trace shows no nested "
            "issue inside an earlier batch's in-flight window"
        )

    stem = out[:-5] if out.endswith(".json") else out
    obs_on.registry.export_json(f"{stem}_metrics.json")
    obs_on.registry.export_prometheus(f"{stem}_metrics.prom")
    n_spans = obs_on.tracer.export_jsonl(f"{stem}_spans.jsonl")
    n_events = obs_on.tracer.export_perfetto(f"{stem}_trace.json")
    n_explains = obs_ex.exemplars.export_json(f"{stem}_explains.json")
    assert n_explains > 0, (
        "explain arm sampled no requests — stride sampler broken?"
    )

    report = {
        "mode": "obs",
        "dataset": dataset,
        "scale": scale,
        "engine": engine,
        "batch_size": batch_size,
        "queries": n_queries,
        "rounds": rounds,
        "device": str(jax.devices()[0]),
        "qps_off": n_queries / wall_off,
        "qps_on": n_queries / wall_on,
        "qps_explain": n_queries / wall_ex,
        "overhead_frac": overhead,
        "explain_overhead_frac": overhead_ex,
        "explain_sample_rate": DEFAULT_EXPLAIN_SAMPLE_RATE,
        "sampled_explains": n_explains,
        "max_overhead": max_overhead,
        "bit_equal": True,
        "overlap_ratio": stats["overlap_ratio"],
        "overlap_visible_in_trace": overlap_ok,
        "spans": n_spans,
        "trace_events": n_events,
        "latency_ms_p50": stats["latency_ms_p50"],
        "latency_ms_p99": stats["latency_ms_p99"],
        "artifacts": [f"{stem}_metrics.json", f"{stem}_metrics.prom",
                      f"{stem}_spans.jsonl", f"{stem}_trace.json",
                      f"{stem}_explains.json"],
    }
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    print(
        f"[obs {engine} bs={batch_size}] off={report['qps_off']:.1f} QPS "
        f"on={report['qps_on']:.1f} QPS overhead={overhead*100:+.1f}% "
        f"(budget {max_overhead*100:.0f}%)  bit_equal=True "
        f"overlap_visible={overlap_ok}  spans={n_spans}"
    )
    print(
        f"[obs explain] qps={report['qps_explain']:.1f} "
        f"overhead={overhead_ex*100:+.1f}% at sample_rate="
        f"{DEFAULT_EXPLAIN_SAMPLE_RATE:.4f}  bit_equal=True "
        f"sampled_explains={n_explains}"
    )
    print(f"[report] -> {out}")
    if gate:
        assert overhead <= max_overhead, (
            f"obs-enabled overhead {overhead*100:.1f}% exceeds the "
            f"{max_overhead*100:.0f}% budget"
        )
        assert overhead_ex <= max_overhead, (
            f"explain-sampled overhead {overhead_ex*100:.1f}% exceeds "
            f"the {max_overhead*100:.0f}% budget"
        )
    return report


def bench_chaos(
    scale: float = 0.2,
    dataset: str = "sift-s",
    batch_size: int = 16,
    engine: str = "jnp",
    k: int = 10,
    smoke: bool = False,
    out: str = "store_chaos.json",
):
    """Chaos soak: a scripted fault matrix against the serving stack.

    Five phases over one collection:

    A. **healthy** — fault-free stream; per-query reference results and
       the healthy p99 baseline every later gate is relative to.
    B. **dispatch raises** — a transient burst (retried, bit-equal), a
       burst long enough to exhaust the retry budget, and one
       non-transient raise (both fail their batch typed).
    C. **latency spikes + brownout** — injected per-step delays breach
       the p99 SLO; the BrownoutController walks the ladder down to the
       floor schedule, which shrinks the injected delay with it (it
       scales with ``plan.steps``, like real schedule cost).  A second
       measurement stream then runs entirely degraded.
    D. **heal** — faults removed; the ladder walks back to healthy and
       results are bit-equal to the reference again.
    E. **snapshot chaos** — the writer is killed at every snapshot-lane
       site (torn leaf, torn manifest, all four crash stages);
       ``restore_collection`` must land on a committed state bit-equal
       to one the writer reached, and sweep the wreckage.

    Gates (hard, non-zero exit on violation):

    * no ticket lost or hung — every submitted ticket terminates with a
      result or a typed error, queues and ring drain to zero;
    * no wrong non-flagged result — every ticket with ``error is None``
      and ``degraded`` False bit-matches the reference for its query;
    * brownout holds the degraded-phase p99 within 2x the healthy
      baseline, and heals back to level 0 once faults stop;
    * every snapshot crash recovers a verified committed state.
    """
    import math as _math
    import os
    import tempfile

    from repro.checkpoint import Checkpointer
    from repro.resilience import BrownoutController, FaultPlan, \
        SimulatedCrash, faults
    from repro.store import restore_collection

    if smoke:
        scale = min(scale, 0.05)
    data, queries = load_dataset(dataset, scale=scale)
    col = Collection.create("chaos", jax.random.key(3), data, c=1.5,
                            t=32, k=k)
    r0, steps = 0.5, 8
    ref_d, ref_i = (np.asarray(x) for x in
                    col.search(queries, k=k, r0=r0, steps=steps,
                               engine=engine))
    nq = queries.shape[0]

    def make_svc(latency_window=64):
        svc = StoreService(
            batch_shapes=(batch_size,), max_wait_ms=1e9, default_k=k,
            r0=r0, steps=steps, engine=engine, inflight_depth=2,
            cache_size=0, latency_window=latency_window,
        )
        svc.attach(col)
        return svc

    all_tickets: list[tuple[str, int, object]] = []

    def run_stream(svc, n, phase):
        for j in range(n):
            qi = j % nq
            all_tickets.append((phase, qi, svc.submit("chaos", queries[qi])))
            if svc.pending() >= batch_size:
                svc.step()
        svc.flush()

    gates: dict[str, bool] = {}
    report: dict = {"dataset": dataset, "scale": scale,
                    "batch_size": batch_size, "engine": engine}

    # ---------------------------------------------------------- A: healthy
    svc = make_svc()
    run_stream(svc, 6 * batch_size, "healthy")
    healthy = svc.stats("chaos")
    p99_healthy = max(healthy["latency_ms_p99"], 2.0)  # sub-ms floors flake
    report["healthy"] = healthy

    # --------------------------------------------------- B: dispatch raises
    svc = make_svc()
    plan = (
        FaultPlan()
        .add("dispatch.raise", at=1, count=2, transient=True)   # retried ok
        .add("dispatch.raise", at=6, count=3, transient=True)   # exhausts
        .add("dispatch.raise", at=12, count=1, transient=False)  # immediate
    )
    n_before = len(all_tickets)
    with faults.active(plan):
        run_stream(svc, 12 * batch_size, "dispatch")
    phase_b = [r for _, _, r in all_tickets[n_before:]]
    b_failed = [r for r in phase_b if r.error is not None]
    gates["dispatch_failures_typed"] = (
        len(b_failed) == 2 * batch_size
        and all(type(r.error).__name__ == "DispatchFailed" for r in b_failed)
        and len(plan.fired) == 6
    )
    report["dispatch"] = {
        "tickets": len(phase_b), "failed_typed": len(b_failed),
        "faults_fired": len(plan.fired), "stats": svc.stats("chaos"),
    }

    # ------------------------------------- C: latency spikes under brownout
    svc = make_svc(latency_window=32)
    bc = BrownoutController(svc, floor_steps=1, heal_after=10**6)
    slo = svc.obs.watch(
        "chaos", latency_p99_ms=2.0 * p99_healthy, min_samples=8,
        check_interval_s=0.0,
    )
    bc.attach(slo)
    # per-step delay: at the full 8-step plan the spike alone is 2x the
    # healthy p99 (breach); at the floor schedule it is 0.25x (headroom)
    spike_per_step = p99_healthy / 4.0
    plan = FaultPlan().add("dispatch.delay_ms", arg=spike_per_step,
                           count=_math.inf)
    with faults.active(plan):
        run_stream(svc, 6 * batch_size, "spike_onset")
        level_engaged = bc.level
        n_before = len(all_tickets)
        run_stream(svc, 6 * batch_size, "spike_degraded")
    degraded_lat = [r.latency_ms for _, _, r in all_tickets[n_before:]]
    p99_degraded = float(np.percentile(degraded_lat, 99))
    gates["brownout_engaged"] = level_engaged >= 2
    gates["brownout_holds_p99"] = p99_degraded <= 2.0 * p99_healthy
    report["brownout"] = {
        "p99_healthy_ms": p99_healthy,
        "p99_degraded_ms": p99_degraded,
        "level_engaged": level_engaged,
        "transitions": bc.transitions,
        "stats": svc.stats("chaos"),
    }

    # ------------------------------------------------------------- D: heal
    bc.heal_after = 2  # chaos over: let the ladder walk back
    run_stream(svc, 8 * batch_size, "heal")
    gates["brownout_heals"] = bc.level == 0
    report["heal"] = {"level_final": bc.level, "transitions": bc.transitions}

    # --------------------------------------------------- E: snapshot chaos
    snap_scenarios = [
        ("torn_leaf", FaultPlan().add(
            "snapshot.write.torn", file="arr_0.npy", arg=64, step=2)),
        ("torn_manifest", FaultPlan().add(
            "snapshot.write.torn", file="manifest.json", arg=32, step=2)),
    ] + [
        (f"crash_{stage}", FaultPlan().add(
            "snapshot.write.crash", stage=stage, step=2))
        for stage in faults.SNAPSHOT_CRASH_STAGES
    ]
    n_half = data.shape[0] // 2
    snap_results = []
    for label, splan in snap_scenarios:
        sdir = tempfile.mkdtemp(prefix=f"chaos_snap_{label}_")
        scol = Collection.create("snap", jax.random.key(5), data[:n_half],
                                 c=1.5, t=16, k=k)
        sref1 = [np.asarray(x) for x in
                 scol.search(queries, k=k, r0=r0, steps=steps)]
        scol.snapshot(sdir)
        scol.add(data[n_half:])
        sref2 = [np.asarray(x) for x in
                 scol.search(queries, k=k, r0=r0, steps=steps)]
        try:
            with faults.active(splan):
                scol.snapshot(sdir)
        except SimulatedCrash:
            pass
        restored = restore_collection(sdir)
        got = [np.asarray(x) for x in
               restored.search(queries, k=k, r0=r0, steps=steps)]
        committed = (
            all(np.array_equal(g, r) for g, r in zip(got, sref1))
            or all(np.array_equal(g, r) for g, r in zip(got, sref2))
        )
        Checkpointer(sdir)  # fresh open sweeps any wreckage
        swept = not any(".tmp" in n for n in os.listdir(sdir))
        snap_results.append(
            {"scenario": label, "recovered_committed": committed,
             "tmp_swept": swept}
        )
        print(f"[snapshot {label:>18s}] committed={committed} swept={swept}")
    gates["snapshot_recovery"] = all(
        s["recovered_committed"] and s["tmp_swept"] for s in snap_results
    )
    report["snapshot"] = snap_results

    # ------------------------------------------------- global ticket gates
    terminated = all(
        r.done and (r.error is not None or r.dists is not None)
        for _, _, r in all_tickets
    )
    clean = [
        (phase, qi, r) for phase, qi, r in all_tickets
        if r.error is None and not r.degraded
    ]
    bit_ok = all(
        np.array_equal(r.dists, ref_d[qi, :k])
        and np.array_equal(r.ids, ref_i[qi, :k])
        for _, qi, r in clean
    )
    gates["no_ticket_lost_or_hung"] = terminated
    gates["non_flagged_results_exact"] = bit_ok
    report["tickets"] = {
        "total": len(all_tickets),
        "clean": len(clean),
        "degraded": sum(1 for _, _, r in all_tickets
                        if r.degraded and r.error is None),
        "failed_typed": sum(1 for _, _, r in all_tickets
                            if r.error is not None),
    }
    report["gates"] = gates
    report["ok"] = all(gates.values())

    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"[chaos] p99 healthy={p99_healthy:.1f}ms "
          f"degraded={report['brownout']['p99_degraded_ms']:.1f}ms "
          f"tickets={report['tickets']}")
    for g, ok in gates.items():
        print(f"[gate] {g}: {'ok' if ok else 'VIOLATED'}")
    print(f"[report] -> {out}")
    if not report["ok"]:
        raise SystemExit("chaos gates violated: " + ", ".join(
            g for g, ok in gates.items() if not ok))
    return report


def bench_sharded_updates(
    scale: float = 0.2,
    dataset: str = "sift-s",
    batch_size: int = 16,
    k: int = 10,
    n_queries: int = 128,
    rounds: int = 4,
    add_batch: int = 64,
    remove_batch: int = 32,
    smoke: bool = False,
    out: str = "store_throughput_sharded.json",
):
    """Mutable-sharded-lifecycle benchmark (+ smoke correctness gate).

    Builds a ShardedCollection over every device the host exposes, then
    interleaves serving with churn: per round, one ``add`` batch (routed
    to the least-loaded shard), one ``remove`` batch (victims drawn from
    live search results, so the ids are always current), and one
    ``compact``.  Mutation timings include the ``live_count`` sync that
    makes the mutation observable — the honest "visible to the next
    query" cost.  Query QPS is measured through the StoreService before
    and after the churn (cache off: mutations would invalidate it anyway,
    and serving repeats would measure the wrong thing).

    Gates: every compact must leave the fleet balanced (max/min live
    ratio <= 1.25 — compaction rebalances, it does not just rebuild in
    place); no deleted point may resurface; post-churn recall vs brute
    force must hold; and a snapshot taken on pn shards must restore
    elastically onto pn//2 with comparable recall.
    """
    if smoke:
        scale, n_queries, rounds = min(scale, 0.05), 32, 2
    data, queries = load_dataset(dataset, scale=scale)
    pn = len(jax.devices())
    mesh = make_mesh((pn,), ("data",))
    n_pool = data.shape[0]
    n_base = (int(n_pool * 0.75) // pn) * pn
    base, pool = data[:n_base], data[n_base:]
    col = ShardedCollection.create(
        "fleet", jax.random.key(1), base, mesh, c=1.5, t=64, k=k,
        payload=np.arange(n_base),  # stable identity across id re-bases
        policy=CompactionPolicy(auto=False),
    )
    svc = _make_service(
        col, batch_size=batch_size, engine="jnp", k=k, r0=0.5, steps=8,
        inflight_depth=2, cache_size=0,
    )

    reps = -(-n_queries // queries.shape[0])
    stream = np.tile(queries, (reps, 1))[:n_queries]
    _stream(svc, "fleet", stream, batch_size)  # warmup compile
    qps_before = n_queries / _stream(svc, "fleet", stream, batch_size)

    alive = np.ones(n_pool, bool)
    alive[n_base:] = False
    next_tag = n_base
    add_s, remove_s, compact_s = [], [], []
    added = removed = 0
    removed_tags_all: set[int] = set()
    for _ in range(rounds):
        mb = min(add_batch, len(pool) - (next_tag - n_base))
        if mb > 0:
            t0 = time.perf_counter()
            col.add(pool[next_tag - n_base:next_tag - n_base + mb],
                    payload=np.arange(next_tag, next_tag + mb))
            col.live_count()  # sync: mutation observable
            add_s.append(time.perf_counter() - t0)
            alive[next_tag:next_tag + mb] = True
            next_tag += mb
            added += mb

        d_l, i_l = map(np.asarray, col.search(queries, k=k, r0=0.5, steps=8))
        victims = np.unique(i_l[np.isfinite(d_l)])[:remove_batch]
        victim_tags = np.asarray(col.get_payload(victims[None]))[0].astype(int)
        t0 = time.perf_counter()
        col.remove(victims.astype(np.int32))
        col.live_count()
        remove_s.append(time.perf_counter() - t0)
        alive[victim_tags] = False
        removed += len(victims)
        removed_tags_all.update(victim_tags.tolist())

        t0 = time.perf_counter()
        col.compact()
        col.live_count()
        compact_s.append(time.perf_counter() - t0)

        # gate: compaction REBALANCES — survivors migrate toward the
        # emptiest shards, so the post-compact fleet is near-uniform
        # however lopsided the preceding adds were
        cts = col.shard_counts()
        cmax, cmin = int(cts.max()), int(cts.min())
        assert cmax - cmin <= 1 or cmax <= 1.25 * max(cmin, 1), (
            f"post-compact shard imbalance {cmax}/{cmin} exceeds 1.25x: "
            f"{cts.tolist()}"
        )

        # gate: no point deleted in ANY round resurfaces after the
        # rebuild (a stale id surviving a later re-base would show up
        # here, not just in this round's victims)
        d_c, i_c = map(np.asarray, col.search(queries, k=k, r0=0.5, steps=8))
        got = np.asarray(col.get_payload(i_c))[np.isfinite(d_c)]
        leaked = set(
            np.asarray(got).reshape(-1).astype(int).tolist()
        ) & removed_tags_all
        assert not leaked, f"deleted points resurfaced: {sorted(leaked)[:8]}"

    # the churn changed n (=> new dispatch shapes): warm the recompile
    # out of the timed post-churn stream so before/after compare steady
    # states, not one-off XLA compiles
    _stream(svc, "fleet", stream, batch_size)
    qps_after = n_queries / _stream(svc, "fleet", stream, batch_size)

    # gate: post-churn recall vs brute force of the surviving point set,
    # matched through the payload tags (adds keep ids stable, but each
    # compact renumbers — tags carry identity across the rebuilds)
    alive_tags = np.flatnonzero(alive)
    _, gt_i = brute_force(data[alive_tags], queries, k=k)
    d_f, i_f = map(np.asarray, col.search(queries, k=k, r0=0.5, steps=8))
    tags_f = np.asarray(col.get_payload(i_f)).astype(int)  # one batched take
    recs = []
    for qi in range(queries.shape[0]):
        got = tags_f[qi][np.isfinite(d_f[qi])]
        want = alive_tags[np.asarray(gt_i)[qi]]
        recs.append(len(set(got.tolist()) & set(want.tolist())) / k)
    rec = float(np.mean(recs))
    assert rec > 0.5, f"post-churn sharded recall@{k} collapsed: {rec:.3f}"
    assert col.live_count() == int(alive.sum())

    # elastic-restore smoke: snapshot on pn shards, restore on pn', and
    # the migrated fleet must answer with comparable recall (identity
    # through the payload tags — the migration renumbers global ids)
    rec_elastic, pn_new, t_restore = float("nan"), 0, float("nan")
    if pn > 1:
        import tempfile

        pn_new = pn // 2
        tmpdir = tempfile.mkdtemp(prefix="sharded_bench_snap_")
        step = col.snapshot(tmpdir)
        mesh2 = make_mesh((pn_new,), ("data",))
        t0 = time.perf_counter()
        col2 = ShardedCollection.restore(tmpdir, mesh=mesh2, step=step)
        col2.live_count()
        t_restore = time.perf_counter() - t0
        assert col2.live_count() == int(alive.sum())
        d_r, i_r = map(np.asarray, col2.search(queries, k=k, r0=0.5, steps=8))
        tags_r = np.asarray(col2.get_payload(i_r)).astype(int)
        recs_r = []
        for qi in range(queries.shape[0]):
            got = tags_r[qi][np.isfinite(d_r[qi])]
            want = alive_tags[np.asarray(gt_i)[qi]]
            recs_r.append(len(set(got.tolist()) & set(want.tolist())) / k)
        rec_elastic = float(np.mean(recs_r))
        assert rec_elastic > 0.5, (
            f"recall collapsed across elastic restore {pn}->{pn_new}: "
            f"{rec_elastic:.3f}"
        )
        del col2

    report = {
        "mode": "sharded_updates",
        "dataset": dataset,
        "scale": scale,
        "shards": pn,
        "n_base": int(n_base),
        "k": k,
        "rounds": rounds,
        "device": str(jax.devices()[0]),
        "query_qps_before": qps_before,
        "query_qps_after": qps_after,
        "add_points_per_s": added / sum(add_s) if add_s else float("nan"),
        "remove_points_per_s": (
            removed / sum(remove_s) if remove_s else float("nan")
        ),
        "compact_wall_s_mean": float(np.mean(compact_s)),
        "post_churn_recall_at_k": rec,
        "live_points": int(alive.sum()),
        "shard_counts": col.shard_counts().tolist(),
        "elastic_restore_shards": pn_new,
        "elastic_restore_wall_s": t_restore,
        "elastic_restore_recall_at_k": rec_elastic,
    }
    print(
        f"[sharded-updates x{pn}] add={report['add_points_per_s']:.0f} pts/s "
        f"remove={report['remove_points_per_s']:.0f} pts/s "
        f"compact={report['compact_wall_s_mean']*1e3:.0f} ms  "
        f"qps {qps_before:.1f} -> {qps_after:.1f}  recall@{k}={rec:.3f}  "
        f"elastic {pn}->{pn_new} recall={rec_elastic:.3f}"
    )
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"[report] -> {out}")
    return report


def main(
    scale: float = 0.2,
    dataset: str = "sift-s",
    batch_sizes: tuple[int, ...] = (16, 32),
    engines: tuple[str, ...] = ("jnp",),
    n_queries: int = 128,
    k: int = 10,
    out: str = "store_throughput.json",
):
    data, queries = load_dataset(dataset, scale=scale)
    col = Collection.create(
        "bench", jax.random.key(1), data, c=1.5, t=64, k=k,
        payload=np.arange(data.shape[0]),  # realistic serving: ids ride along
    )
    # sanity: the collection actually answers (recall floor, not perf)
    d_, i_ = col.search(queries, k=k, r0=0.5, steps=8)
    gt_d, gt_i = brute_force(data, queries, k=k)
    rec, _ = recall_and_ratio(d_, i_, gt_d, gt_i, k)

    results = []
    speedups = []
    for engine in engines:
        for bs in batch_sizes:
            by_mode = _bench_modes(
                col, queries, batch_size=bs, engine=engine, k=k,
                n_queries=n_queries, r0=0.5, steps=8,
            )
            for mode, pt in by_mode.items():
                results.append(pt)
                print(
                    f"[{engine} bs={bs:3d} {mode:>10s}] "
                    f"{pt['sustained_qps']:8.1f} QPS  "
                    f"p50={pt['latency_ms_p50']:.1f}ms "
                    f"p99={pt['latency_ms_p99']:.1f}ms  "
                    f"overlap={pt['overlap_ratio']:.2f} "
                    f"cache={pt['cache_hit_rate']:.2f}"
                )
            speedups.append({
                "engine": engine,
                "batch_size": bs,
                "overlapped_vs_sync": (
                    by_mode["overlapped"]["sustained_qps"]
                    / by_mode["sync"]["sustained_qps"]
                ),
                "cached_vs_sync": (
                    by_mode["cached"]["sustained_qps"]
                    / by_mode["sync"]["sustained_qps"]
                ),
            })

    tenants = _bench_tenants(
        col, queries, batch_size=batch_sizes[0], engine=engines[0], k=k,
        n_queries=n_queries, r0=0.5, steps=8,
    )
    for t, s in tenants["per_tenant"].items():
        print(f"[tenant {t:>8s}] served={s['served']} rejected={s['rejected']} "
              f"qps={s['qps']:.1f}")

    report = {
        "dataset": dataset,
        "scale": scale,
        "n": int(data.shape[0]),
        "d": int(data.shape[1]),
        "k": k,
        "recall_at_k": rec,
        "device": str(jax.devices()[0]),
        "results": results,
        "speedups": speedups,
        "tenants": tenants,
    }
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"[report] recall@{k}={rec:.3f} -> {out}")
    return report


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.2)
    ap.add_argument("--dataset", default="sift-s")
    ap.add_argument("--batch-sizes", type=int, nargs="+", default=[16, 32])
    ap.add_argument("--engines", nargs="+", default=["jnp"])
    ap.add_argument("--n-queries", type=int, default=128)
    ap.add_argument("--sharded-updates", action="store_true",
                    help="benchmark the mutable sharded lifecycle "
                         "(add/remove/compact throughput + query QPS) "
                         "instead of the scheduler modes")
    ap.add_argument("--obs", action="store_true",
                    help="observability benchmark: obs-on vs obs-off QPS "
                         "with bit-equality + trace/metrics artifacts")
    ap.add_argument("--gate", action="store_true",
                    help="with --obs: hard-fail if enabled overhead "
                         "exceeds --max-overhead (CI)")
    ap.add_argument("--max-overhead", type=float, default=0.05)
    ap.add_argument("--chaos", action="store_true",
                    help="chaos soak: scripted fault matrix (dispatch "
                         "raises, latency spikes + brownout, snapshot "
                         "crashes) with hard recovery gates")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny run with correctness gates (CI) — applies "
                         "to --sharded-updates and --chaos")
    ap.add_argument("--out", default="store_throughput.json")
    args = ap.parse_args()
    if args.chaos:
        bench_chaos(
            scale=args.scale,
            dataset=args.dataset,
            batch_size=args.batch_sizes[0],
            engine=args.engines[0],
            smoke=args.smoke,
            out=args.out if args.out != "store_throughput.json"
            else "store_chaos.json",
        )
    elif args.obs:
        bench_obs(
            scale=args.scale,
            dataset=args.dataset,
            batch_size=args.batch_sizes[0],
            engine=args.engines[0],
            n_queries=args.n_queries,
            max_overhead=args.max_overhead,
            gate=args.gate,
            out=args.out if args.out != "store_throughput.json"
            else "store_obs.json",
        )
    elif args.sharded_updates:
        bench_sharded_updates(
            scale=args.scale,
            dataset=args.dataset,
            batch_size=args.batch_sizes[0],
            n_queries=args.n_queries,
            smoke=args.smoke,
            out=args.out,
        )
    else:
        main(
            scale=args.scale,
            dataset=args.dataset,
            batch_sizes=tuple(args.batch_sizes),
            engines=tuple(args.engines),
            n_queries=args.n_queries,
            out=args.out,
        )
