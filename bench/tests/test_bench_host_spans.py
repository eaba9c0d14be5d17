"""The readers of the program's host spans: on synthetic span lists, on
the spans of a program that lacks them, and on a recording from the chip
(``data/sift1m_host_v5e.*``): the program's batch-level spans and
``clock.sync`` records of a short ``--trace 1`` window of
``sift1m.closed64`` on one TPU v5 lite, beside the profiler's host plane
cut to its ``store.dispatch.*`` events."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, trace_reduce  # noqa: E402

DATA = ROOT / "bench" / "tests" / "data"
SIDE = json.loads((DATA / "sift1m_host_v5e.json").read_text())
NEW = ("upload_ms_per_batch", "dispatch_ms_per_batch", "gc_ms_per_batch",
       "candidates_per_query")


def _span(name, ts, dur, **args):
    return SimpleNamespace(name=name, ts=ts, dur=dur, args=args)


def _read(name, spans):
    ctx = harness.LayerContext({}, {}, 1.0, spans, None, {}, harness.work_module)
    return harness.load_module(ROOT / "bench" / "layer_metrics" / f"{name}.py").read(ctx)


def _batches(n):
    return [_span("batch.issue", 0.1 * i, 0.002, seq=i, rows=32, shape=32) for i in range(n)]


def test_readers_on_synthetic_spans():
    spans = _batches(2) + [
        _span("clock.sync", 0.0, 0.0, wall_ns=10**18),
        _span("issue.upload", 0.0, 1e-4), _span("issue.upload", 0.1, 3e-4),
        _span("issue.dispatch", 0.0, 1e-3), _span("issue.dispatch", 0.1, 2e-3),
        _span("host.gc", 0.05, 1e-3, generation=0, collected=3),
        _span("batch.complete", 0.02, 1e-3, seq=0, rows=3, candidates=300, steps=9),
        _span("batch.complete", 0.12, 1e-3, seq=1, rows=1, candidates=100, steps=3),
    ]
    assert _read("upload_ms_per_batch", spans) == pytest.approx(0.2)
    assert _read("dispatch_ms_per_batch", spans) == pytest.approx(1.5)
    assert _read("gc_ms_per_batch", spans) == pytest.approx(0.5)
    assert _read("candidates_per_query", spans) == pytest.approx(100.0)
    # a window without a collector pass reads 0 where the tracer records them
    no_gc = [s for s in spans if s.name != "host.gc"]
    assert _read("gc_ms_per_batch", no_gc) == 0.0


def test_readers_read_nothing_on_a_program_without_the_records():
    """The spans the parent program records: the new readers return
    nothing there, and raise nothing."""
    spans = _batches(3) + [
        _span("batch.assemble", 0.0, 1e-4, seq=0, rows=32, shape=32),
        _span("batch.pending", 0.002, 0.01, seq=0),
        _span("batch.complete", 0.012, 1e-3, seq=0, rows=32),
        _span("request.queue_wait", 0.0, 1e-3, uid=1),
    ]
    for name in NEW:
        assert _read(name, spans) is None, name
        assert _read(name, []) is None, name


def _recorded_spans():
    return [SimpleNamespace(**json.loads(line))
            for line in (DATA / "sift1m_host_v5e.spans.jsonl").read_text().splitlines()]


def _dispatch_starts_ns():
    """Absolute starts (ns since the epoch) of the host plane's
    ``store.dispatch.<collection>`` events."""
    space = trace_reduce.read_space(str(DATA / "sift1m_host_v5e.xplane.pb"))
    start = 0
    for plane in space.planes:
        if plane.name == "Task Environment":
            names = {k: v.name for k, v in plane.stat_metadata.items()}
            for st in plane.stats:
                if names.get(st.metadata_id) == "profile_start_time":
                    start = st.uint64_value or st.int64_value
    assert start > 0
    out = []
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        names = {k: v.name for k, v in plane.event_metadata.items()}
        for line in plane.lines:
            out += [start + line.timestamp_ns + ev.offset_ps / 1e3 for ev in line.events
                    if names[ev.metadata_id] == f"store.dispatch.{SIDE['collection']}"]
    return sorted(out)


def test_clock_sync_puts_issue_spans_on_the_profiler_clock():
    """Each ``batch.issue`` start, mapped through the nearest
    ``clock.sync`` alone, lies within 100 us of the host plane's
    ``store.dispatch`` event the issue wraps."""
    spans = _recorded_spans()
    syncs = [s for s in spans if s.name == "clock.sync"]
    assert syncs

    def wall_ns(ts):
        sync = min(syncs, key=lambda s: abs(s.ts - ts))
        return ts * 1e9 + sync.args["wall_ns"] - sync.ts * 1e9

    events = _dispatch_starts_ns()
    issues = [wall_ns(s.ts) for s in spans if s.name == "batch.issue"]
    inside = [t for t in issues if events[0] - 1e6 <= t <= events[-1] + 1e6]
    assert len(inside) >= SIDE["batches"] and len(events) >= SIDE["batches"]
    residual = [min(abs(e - t) for e in events) for t in inside]
    assert max(residual) <= 100e3, max(residual)


def test_readers_on_the_recording():
    spans = _recorded_spans()
    host = harness.load_module(ROOT / "bench" / "layer_metrics" / "host_ms_per_batch.closed.py")
    ctx = harness.LayerContext({}, {}, 1.0, spans, None, {}, harness.work_module)
    host_ms = host.read(ctx)
    split = _read("upload_ms_per_batch", spans) + _read("dispatch_ms_per_batch", spans)
    assert 0 < split <= host_ms
    assert 0 < _read("candidates_per_query", spans) <= SIDE["candidates_cap"]
    assert _read("gc_ms_per_batch", spans) >= 0
    # every upload and dispatch is a child of its batch's issue, on its lane
    issue = {s.sid: s for s in spans if s.name == "batch.issue"}
    for s in spans:
        if s.name in ("issue.upload", "issue.dispatch"):
            parent = issue[s.parent]
            assert s.tid == parent.tid
            assert parent.ts <= s.ts and s.ts + s.dur <= parent.ts + parent.dur + 1e-9
