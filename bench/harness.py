"""One run of one cell: set up, warm up, measure, check, print.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the cell's entry in
``BENCHMARK.json`` names its configuration (``configs[].file``) and its
traffic mix (``bench/traffic/<traffic>.json``); each per-layer metric is
a reader ``bench/layer_metrics/<metric>.py``; each kernel's work count is
``bench/work/<kernel>.py``; the chip's peaks are ``bench/peaks.json``.

Phases:

1. set-up (``setup_s``): data, query pools and the index from the seed
   on the cell's devices; the service; every batch shape and every
   batch fill the traffic can produce, dispatched once through the
   service, so that nothing compiles in the window.
2. the window: ``--seconds`` of the traffic mix through
   ``StoreService.submit`` / ``step``; with ``--trace 1`` under the JAX
   profiler and the program's tracer.
3. after the window: memory is read, the program's state is freed, and
   a sample of the window's answers is compared with the exact
   reference (``check.py``).

The last line of standard output is the result; the numbers compared
are printed beside their limits as the last lines of standard error and
under ``checks``, the result's last key.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from . import traffic as traffic_mod

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SAMPLE = 4000        # distinct queries compared with the reference per run
SAMPLE_FROM = 8000   # ... drawn among this many first requests of the window
IDLE_S = 5e-4        # the closed loop's pause when it has nothing to send


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ the spec
@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(bench: dict, workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload``, with every file it names loaded."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    mix = traffic_mod.load(root / "bench" / "traffic" / f"{w['traffic']}.json")

    def mine(m):
        return workload in m.get("workloads", [workload])

    per_layer = [m for m in bench["per_layer"] if mine(m)]
    for m in per_layer:
        path = root / "bench" / "layer_metrics" / f"{m['name']}.py"
        if not path.is_file():
            raise FileNotFoundError(f"per-layer metric {m['name']!r}: no reader {path}")
    return Cell(workload, int(w["chips"]), config, mix,
                [m for m in bench["end_to_end"] if mine(m)], per_layer)


# ------------------------------------------------------------- the program
def cell_devices(config: dict, devices):
    """The devices in the order the data's row shards are laid on them,
    and the mesh of a sharded placement (else ``None``).  ``make_mesh``
    orders a v5e 2x2 as [0, 1, 3, 2]: shard r must be drawn on the mesh's
    r-th device for the global row numbers, and so the reference's, to
    hold."""
    if config["placement"] == "local":
        return list(devices[:1]), None
    from repro.compat import make_mesh

    mesh = make_mesh((len(devices),), ("data",), devices=devices)
    return list(mesh.devices.flat), mesh


def build(config: dict, shards, key, mesh):
    """The collection the config describes, over the data shards."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.store import Collection, ShardedCollection

    index_kw = {k: config["index"][k]
                for k in ("c", "t", "k", "K", "L", "block_size", "max_blocks")}
    name = config["name"]
    if config["placement"] == "local":
        return Collection.create(
            name, key, shards[0], inline_vectors=config["index"]["inline_vectors"],
            engine=config["service"]["engine"], **index_kw)
    n = sum(s.shape[0] for s in shards)
    data = jax.make_array_from_single_device_arrays(
        (n, shards[0].shape[1]), NamedSharding(mesh, P("data")), shards)
    return ShardedCollection.create(
        name, key, data, mesh, payload=np.arange(n, dtype=np.int32), **index_kw)


# ------------------------------------------------------------------ window
class Ledger:
    """What the harness keeps of each request of the window, in plain
    arrays: a ticket is dropped as soon as it is read, so that nothing the
    collector tracks piles up over the window and its full passes stay as
    short as they would be in a server that does not hold every answer."""

    def __init__(self, k: int, cap: int = 1 << 14):
        self.n, self.k = 0, k
        self.seq = np.zeros(cap, np.int64)       # submission order
        self.row = np.zeros(cap, np.int64)       # query pool row
        self.due = np.zeros(cap)                 # monotonic s: due (open) or submitted (closed)
        self.done_at = np.zeros(cap)             # monotonic s; inf if it never completed
        self.ok = np.zeros(cap, bool)            # completed, no error, not degraded
        self.cached = np.zeros(cap, bool)
        self.ids = np.zeros((cap, k), np.int64)
        self.rows = np.zeros((cap, k), np.int64)  # data rows: the payload, else the ids
        self.dists = np.zeros((cap, k), np.float32)

    def _grow(self):
        for name in ("seq", "row", "due", "done_at", "ok", "cached", "ids", "rows", "dists"):
            a = getattr(self, name)
            setattr(self, name, np.concatenate([a, np.zeros_like(a)]))

    def add(self, seq: int, row: int, due: float, ticket) -> None:
        if self.n == self.seq.size:
            self._grow()
        i = self.n
        self.n += 1
        self.seq[i], self.row[i], self.due[i] = seq, row, due
        if not ticket.done:
            self.done_at[i], self.ok[i] = np.inf, False
            return
        self.done_at[i] = ticket.submitted + ticket.latency_ms / 1e3
        self.ok[i] = ticket.error is None and not ticket.degraded
        self.cached[i] = bool(ticket.cached)
        if self.ok[i]:
            self.ids[i] = ticket.ids
            self.dists[i] = ticket.dists
            self.rows[i] = ticket.payload if ticket.payload is not None else ticket.ids

    def view(self, name: str) -> np.ndarray:
        return getattr(self, name)[: self.n]


@dataclass
class Window:
    t_open: float
    t_close: float
    ledger: Ledger
    open: list = field(default_factory=list)   # (seq, row, due, ticket) not yet read
    lateness: list = field(default_factory=list)

    def read_done(self) -> None:
        """Move every completed request from ``open`` to the ledger."""
        still = []
        for entry in self.open:
            if entry[3].done:
                self.ledger.add(*entry)
            else:
                still.append(entry)
        self.open = still

    def finish(self) -> None:
        """After the service is flushed: every request goes to the ledger,
        a request that never completed as such."""
        for entry in self.open:
            self.ledger.add(*entry)
        self.open = []


def run_closed(svc, name: str, mix: dict, pool: np.ndarray, order, seconds: float,
               clock, ledger: Ledger) -> Window:
    """Each client sends its next query when its previous one completes;
    a request is due when it is submitted."""
    clients = int(mix["clients"])
    tenants = [traffic_mod.tenant(mix, c) for c in range(clients)]
    win = Window(clock(), 0.0, ledger)
    t_end = win.t_open + seconds
    seq = 0

    def send(c):
        nonlocal seq
        row = int(order.take(1)[0])
        ticket = svc.submit(name, pool[row], tenant=tenants[c])
        seq += 1
        return (seq - 1, row, ticket.submitted, ticket)

    live = [send(c) for c in range(clients)]
    while True:
        svc.step()
        if clock() >= t_end:
            break
        sent = False
        for c, entry in enumerate(live):
            if entry[3].done:
                ledger.add(*entry)
                live[c] = send(c)
                sent = True
        # every client waits on a batch in flight: yield the host's core
        # to the runtime rather than spin on the service
        if not sent and not svc.pending():
            time.sleep(IDLE_S)
    win.t_close = clock()
    win.open = live
    return win


def run_open(svc, name: str, mix: dict, pool: np.ndarray, order, seconds: float,
             seed: int, clock, ledger: Ledger) -> Window:
    """Every request is sent at its due time, or as soon after it as the
    loop gets round to it; a request still unsent when the window closes
    is sent late, and its latency counts the wait."""
    offsets = traffic_mod.due_times(mix, seed, seconds)
    rows = order.take(offsets.size)
    win = Window(clock(), 0.0, ledger)
    due = offsets + win.t_open
    win.t_close = win.t_open + seconds
    i = 0
    while i < due.size or clock() < win.t_close:
        now = clock()
        while i < due.size and due[i] <= now:
            win.lateness.append(clock() - due[i])
            ticket = svc.submit(name, pool[rows[i]], tenant=traffic_mod.tenant(mix, i))
            win.open.append((i, int(rows[i]), float(due[i]), ticket))
            i += 1
        svc.step()
        win.read_done()
    return win


def latencies(win: Window) -> tuple[np.ndarray, np.ndarray]:
    """When each request of the window completed (the service's clock:
    its submit time plus its latency; infinity if it never did), and its
    latency in ms from when it was due, in submission order."""
    led = win.ledger
    order = np.argsort(led.view("seq"), kind="stable")
    done_at = led.view("done_at")[order]
    return done_at, (done_at - led.view("due")[order]) * 1e3


# ------------------------------------------------------------------- trace
@dataclass
class LayerContext:
    """What a per-layer reader may read."""

    config: dict
    traffic: dict
    window_s: float
    spans: list            # the program's tracer spans inside the window
    device: object         # trace_reduce.DeviceTrace or None
    peaks: dict            # this chip's row of peaks.json
    work: callable         # kernel name -> its bench/work module

    def batches(self) -> list:
        """The program's ``batch.issue`` spans: one per dispatched batch."""
        return [s for s in self.spans if s.name == "batch.issue"]


def work_module(kernel: str):
    return load_module(BENCH / "work" / f"{kernel}.py")


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


# --------------------------------------------------------------------- run
def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool, devices,
             t_start: float, interpret=None, trace_dir: str | None = None,
             fault=None) -> dict:
    """One run of ``cell``; returns the result line as a dict.

    ``fault`` (``faults.py``; never in the benchmark's own runs) is called
    with the collection, the service and the data before the warm-up, and
    breaks the timed path underneath."""
    import jax

    from repro.obs import Observability
    from repro.obs.trace import Tracer
    from repro.store import StoreService

    from . import check, trace_reduce
    from .data import make_vectors

    cfg, mix = cell.config, cell.traffic
    devices, mesh = cell_devices(cfg, devices)
    clock = time.monotonic
    dev0 = devices[0]
    peaks = peaks_for(dev0.device_kind) if dev0.platform == "tpu" else {}
    svc_cfg = cfg["service"]
    shapes = tuple(svc_cfg["batch_shapes"])
    n_warm = shapes[-1] * (shapes[-1] + 1) // 2
    d = int(cfg["data"]["d"])

    t = time.perf_counter()
    shards, pools, _, key = make_vectors(
        seed, n=int(cfg["data"]["n"]), d=d, n_clusters=int(cfg["data"]["clusters"]),
        spread=float(cfg["data"]["spread"]),
        pools={"window": int(mix["queries"]["pool"]), "warm": n_warm}, devices=devices)
    jax.block_until_ready(shards)
    say(f"data: {cfg['data']['n']} x {d} over {len(devices)} device(s) + pools "
        f"{ {k: v.shape[0] for k, v in pools.items()} } in {time.perf_counter() - t:.2f} s")

    t = time.perf_counter()
    col = build(cfg, shards, key, mesh)
    jax.block_until_ready(jax.tree_util.tree_leaves(
        col.index if hasattr(col, "index") else col.sharded.index))
    say(f"build: {time.perf_counter() - t:.2f} s")

    tracer = Tracer(enabled=trace, sample_rate=1.0, maxlen=1 << 22)
    svc = StoreService(
        batch_shapes=shapes, inflight_depth=int(svc_cfg["inflight_depth"]),
        max_wait_ms=float(svc_cfg["max_wait_ms"]), default_k=int(cfg["index"]["k"]),
        r0=float(svc_cfg["r0"]), steps=int(svc_cfg["steps"]),
        engine=svc_cfg["engine"], interpret=interpret,
        cache_size=int(svc_cfg["cache_size"]), obs=Observability(tracer=tracer))
    svc.attach(col)
    for tenant_name, weight in mix["tenants"].items():
        svc.set_quota(tenant_name, weight=int(weight))

    if fault is not None:
        fault(col, svc, shards)

    # every fill 1..max of every batch shape, once, through the service:
    # the dispatch compiles per shape and the payload gather per fill
    warm = pools["warm"]
    t, w = time.perf_counter(), 0
    for m in range(1, shapes[-1] + 1):
        t_m = time.perf_counter()
        for q in warm[w:w + m]:
            svc.submit(cfg["name"], q, tenant=traffic_mod.tenant(mix, 0))
        w += m
        svc.flush()
        shape = min(s for s in shapes if s >= m)
        if m == 1 or shape > min(s for s in shapes if s >= m - 1):
            say(f"warm-up: first dispatch of shape {shape}: {time.perf_counter() - t_m:.2f} s")
    say(f"warm-up: fills 1..{shapes[-1]} in {time.perf_counter() - t:.2f} s")

    compiles = []

    def on_compile(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    cache0 = dict(svc.cache_stats())
    # set-up leaves many long-lived objects (traced programs, the index's
    # host state): keep them out of the collector's later passes
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    say(f"setup_s {setup_s:.3f}")

    # ---------------------------------------------------------- window
    pool = pools["window"]
    order = traffic_mod.QueryOrder(mix, seed)
    tmp = None
    if trace:
        tmp = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
        tracer.clear()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tmp, profiler_options=opts)
        wall_minus_mono = time.time_ns() - time.monotonic_ns()
    n_compiles0 = len(compiles)
    k = int(cfg["index"]["k"])
    ledger = Ledger(k)
    if mix["loop"] == "closed":
        win = run_closed(svc, cfg["name"], mix, pool, order, seconds, clock, ledger)
    else:
        win = run_open(svc, cfg["name"], mix, pool, order, seconds, seed, clock, ledger)
    if trace:
        jax.profiler.stop_trace()
    svc.flush()
    win.finish()
    in_window_compiles = len(compiles) - n_compiles0
    jax.monitoring.unregister_event_duration_listener(on_compile)
    cache1 = dict(svc.cache_stats())
    window_s = win.t_close - win.t_open

    # ----------------------------------------------------- the readings
    done_at, latency_ms = latencies(win)
    led = win.ledger
    failed = int(np.sum(~led.view("ok")))
    completed = int(np.sum(done_at <= win.t_close))
    stats = [s.memory_stats() or {} for s in devices]
    hbm = max(s.get("bytes_in_use", 0) for s in stats)
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    if win.lateness:
        say(f"generator lateness: p50 {percentile(win.lateness, 50) * 1e3:.3f} ms, "
            f"p99 {percentile(win.lateness, 99) * 1e3:.3f} ms, "
            f"max {max(win.lateness) * 1e3:.3f} ms over {len(win.lateness)} sends")
    say(f"window: {led.n} requests, {completed} completed in {window_s:.3f} s, "
        f"{failed} failed; cache {cache1['hits'] - cache0['hits']} hits / "
        f"{cache1['misses'] - cache0['misses']} misses; {in_window_compiles} compiles")

    # cache hits against the search path's answer for the same query:
    # taken in completion order, a hit must repeat the last miss bit for bit
    good = np.nonzero(led.view("ok"))[0]
    last, hit_mismatch = {}, 0
    ids, dists, cached = led.view("ids"), led.view("dists"), led.view("cached")
    for i in sorted(good, key=lambda i: (led.done_at[i], cached[i])):
        ans = ids[i].tobytes() + dists[i].tobytes()
        if cached[i]:
            hit_mismatch += last.get(led.row[i]) != ans
        else:
            last[led.row[i]] = ans
    # the sample: distinct queries, drawn from the seed among the first
    # SAMPLE_FROM requests sent, which every run of a seed sends alike, so
    # that two runs of one seed compare the same queries; a repeated query
    # counts once, however popular
    first = good[led.seq[good] < SAMPLE_FROM]
    first = first[np.argsort(led.seq[first], kind="stable")]
    _, once = np.unique(led.row[first], return_index=True)
    first = first[np.sort(once)]
    rng = np.random.default_rng([int(seed), 3])
    pick = first[np.sort(rng.choice(first.size, size=min(SAMPLE, first.size), replace=False))]
    s_q = pool[led.row[pick]]
    s_rows, s_d = led.rows[pick], led.dists[pick]
    spans = [s for s in tracer.events if win.t_open <= s.ts <= win.t_close]
    del svc, col
    gc.unfreeze()
    gc.collect()

    t = time.perf_counter()
    checks, correct, spread = check.compare(shards, s_q, s_rows, s_d, hit_mismatch,
                                            cfg["correct"], k)
    say("distance gaps (relative): " + ", ".join(f"{q} {v!r}" for q, v in spread.items()))
    say(f"reference over {len(pick)} sampled answers: {time.perf_counter() - t:.2f} s")

    e2e = {
        "qps": completed / window_s,
        "p99_ms": percentile(latency_ms, 99),
        "recall_at_10": checks["recall"]["value"],
        "hbm_gib": hbm / 2**30,
        "setup_s": setup_s,
    }
    say(f"p50_ms {percentile(latency_ms, 50):.3f} over {latency_ms.size} requests")
    result = {
        "correct": bool(correct),
        "attempted": len(latency_ms),
        "failed": int(failed),
        "metrics": {},
        "device": {"platform": dev0.platform, "kind": dev0.device_kind,
                   "count": len(devices), "memory_peak_bytes": int(peak)},
    }
    if not trace:
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        dt = trace_reduce.load(tmp, t0_ns=win.t_open * 1e9 + wall_minus_mono,
                               t1_ns=win.t_close * 1e9 + wall_minus_mono,
                               n_devices=len(devices))
        if trace_dir is None:
            shutil.rmtree(tmp, ignore_errors=True)
        ctx = LayerContext(cfg, mix, window_s, spans, dt if dt.found else None, peaks,
                           work_module)
        for m in cell.per_layer:
            value = load_module(BENCH / "layer_metrics" / f"{m['name']}.py").read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        result["device"]["busy_s"] = dt.busy_s
        result["device"]["window_s"] = dt.window_s
        result["breakdown"] = trace_reduce.breakdown(dt, spans, wall_minus_mono)
        by_cat: dict = {}
        for o in dt.ops:
            by_cat[o.category] = by_cat.get(o.category, 0.0) + o.dur_ns / 1e9 / len(devices)
        say("device seconds by op category: " + ", ".join(
            f"{k or '?'} {v:.4f}" for k, v in sorted(by_cat.items(), key=lambda kv: -kv[1])))
    result["checks"] = {k: {"value": v["value"], "limit": v["limit"]} for k, v in checks.items()}
    for k, v in checks.items():
        say(f"check {k}: {v['value']!r} limit {v['limit']!r} {'ok' if v['ok'] else 'FAILED'}")
    return result


def chip_devices(cell: Cell):
    """The cell's devices, with the compile cache set up; ``None`` (after
    saying why) when jax finds no TPU or too few chips."""
    import os

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (jax found {devices[0].platform}); the benchmark runs "
              "on the chip only", file=sys.stderr)
        return None
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, jax found "
              f"{len(devices)}", file=sys.stderr)
        return None
    sys.path.insert(0, str(ROOT / "src"))
    # the cache directory is fixed (its path is part of the key); every
    # program is cached, however fast it compiles, so that a run's set-up
    # compiles nothing the checkout's first run compiled
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    say(f"device {devices[0].device_kind} x {len(devices)}; compile cache {cache_dir}")
    return devices[:cell.chips]


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description="One run of one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = resolve(json.loads((ROOT / "BENCHMARK.json").read_text()), args.workload)
    devices = chip_devices(cell)
    if devices is None:
        return 2
    result = run_cell(cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                      devices=devices, t_start=t_start, interpret=False)
    print(json.dumps(result), flush=True)
    return 0
