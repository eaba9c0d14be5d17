"""Low-overhead span tracing for the serving stack.

Every interesting interval in a request's life becomes a typed
:class:`Span` on one process timeline, answerable to "where did this
query's 4 ms go?" without re-running a benchmark.  The serving path
records (DESIGN.md §10):

* per request: ``request.queue_wait`` (submit -> drain) and
  ``request.done``, an instant at completion carrying ``submitted``,
  ``latency_ms``, ``cached`` and ``batch_seq``, which the Perfetto export
  renders as the request's submit -> done slice;
* per batch, on the scheduler lane: ``batch.drain`` (weighted
  round-robin drain and the deadline gate), ``cache.lookup`` (args
  ``probes`` / ``hits``), ``batch.assemble`` (host padding);
* per batch, on its ring lane: ``batch.issue`` with children
  ``issue.upload`` (the query matrix to the device), ``issue.dispatch``
  (the jitted search call, until it returns futures) and
  ``issue.payload`` (the payload gather), then ``batch.complete`` (args
  ``rows`` / ``candidates`` / ``steps``) with children
  ``complete.fetch`` (device-to-host reads) and ``complete.tickets``
  (tickets, cache puts, stats), both on the scheduler lane;
* per process, while any tracer is enabled: ``host.gc`` (one Python
  collector pass, args ``generation`` / ``collected``) and
  ``jit.compile`` (one XLA backend compile), so an idle device gap can
  be named by what the host was doing in it;
* ``clock.sync``: an instant pairing this tracer's clock with
  ``time.time_ns()``, the clock of ``jax.profiler`` traces, recorded
  whenever tracing starts (construction enabled, :meth:`Tracer.enable`,
  :meth:`Tracer.clear`) and then at most once a second from
  :meth:`Tracer.add_span`; any window of spans therefore carries its own
  offset onto the device trace's timeline;
* lifecycle mutations (``lifecycle.*``) on the tracer of the service a
  collection is attached to (the process-global tracer otherwise).

There is no span for the time a batch sits issued but not yet
completed: its interval is ``batch.issue``'s end to ``batch.complete``'s
start, by ``seq``, and a waiting span would name every device-idle gap
after the wait rather than after the host work that caused it.

Design constraints:

* **Cheap when off.**  The tracer is disabled by default; every hot-path
  call site guards on ``tracer.enabled`` (one attribute read) or goes
  through :meth:`Tracer.add_span`, which returns immediately when
  disabled.  The collector hook is installed only while a tracer is
  enabled, and the compile listener runs only on a compile.  Enabling
  must not change results: spans only *observe* timestamps.
* **Two-phase spans.**  The scheduler's overlapped dispatch means spans
  do not nest lexically (batch N+1 is issued while batch N is still
  in flight), so the recorder accepts explicit ``(t_start, t_end)``
  intervals (:meth:`add_span`, which returns the span id and takes a
  ``parent``) next to the context-manager form (:meth:`span`) used by
  synchronous work like lifecycle mutations.  A parent recorded after
  its children reserves its id first (:meth:`new_id`); code called
  inside a :meth:`children` block records with ``**tracer.scope`` to
  become that parent's child on its lane.
* **Lanes.**  Each span carries a ``tid`` (track id).  The scheduler
  puts its own host work on :data:`TID_SCHEDULER` and each in-flight
  batch on ``TID_RING0 + ring-slot``, so a Perfetto render shows the
  overlap directly: the issue span of batch N+1 sits one lane up, while
  batch N is still in flight.
* **Bounded.**  The event buffer is a ring (``maxlen``); a long-lived
  serving process can leave tracing on without growing memory.

Exports: :meth:`Tracer.export_jsonl` (one span per line, the full
record, ``clock.sync`` instants included) and
:meth:`Tracer.export_perfetto` (Chrome ``trace_event`` JSON, with the
clock offset under ``otherData`` -- load in ``ui.perfetto.dev`` or
``chrome://tracing``).  Request spans (``cat == "request"``) export as
*async* event pairs so hundreds of concurrently-queued requests render
as overlapping slices instead of fighting over one track.

Device correlation: the jitted dispatch is wrapped in
``jax.profiler.TraceAnnotation`` (host side) and the search stages carry
``jax.named_scope`` labels (HLO metadata); ``clock.sync`` puts the spans
on the profiler's clock: ``wall_ns = ts * 1e9 + (wall_ns - ts * 1e9)``
of the nearest sync.
"""

from __future__ import annotations

import gc
import json
import time
import weakref
from collections import deque
from contextlib import contextmanager

__all__ = [
    "Span",
    "Tracer",
    "get_tracer",
    "wall_offset_ns",
    "watch_compiles",
    "TID_SCHEDULER",
    "TID_RING0",
    "TID_LIFECYCLE",
]

# Track (lane) assignment for the Perfetto timeline.  Ring lanes are
# TID_RING0 + slot so a depth-d ring renders as d parallel device lanes.
TID_SCHEDULER = 0
TID_RING0 = 1
TID_LIFECYCLE = 64

# seconds of a tracer's clock between two of its ``clock.sync`` records
SYNC_EVERY_S = 1.0

_TRACK_NAMES = {
    TID_SCHEDULER: "scheduler (host)",
    TID_LIFECYCLE: "lifecycle",
}


class Span:
    """One recorded interval (or instant, when ``dur`` is 0 and
    ``ph == 'i'``).  Plain ``__slots__`` object — spans are allocated on
    the serving path and must stay cheap."""

    __slots__ = ("name", "cat", "ts", "dur", "tid", "sid", "parent", "args", "ph")

    def __init__(self, name, cat, ts, dur, tid, sid, parent, args, ph="X"):
        self.name = name
        self.cat = cat
        self.ts = ts          # seconds, tracer clock
        self.dur = dur        # seconds
        self.tid = tid
        self.sid = sid        # unique span id
        self.parent = parent  # enclosing span id, or None
        self.args = args
        self.ph = ph          # "X" complete | "i" instant

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "cat": self.cat,
            "ts": self.ts,
            "dur": self.dur,
            "tid": self.tid,
            "sid": self.sid,
            "parent": self.parent,
            "ph": self.ph,
            "args": self.args,
        }


class _NopSpan:
    """Handle yielded by ``span()`` when tracing is off."""

    __slots__ = ()

    def set(self, **kw) -> None:
        pass


_NOP = _NopSpan()


class _LiveSpan:
    """Handle yielded by ``span()`` while the interval is open; ``set``
    attaches args discovered mid-span (e.g. how many rows a compaction
    actually moved)."""

    __slots__ = ("args",)

    def __init__(self, args: dict):
        self.args = args

    def set(self, **kw) -> None:
        self.args.update(kw)


class Tracer:
    """Bounded span recorder with an injectable clock.

    ``enabled`` gates everything; ``sample_rate`` (0..1) additionally
    thins *request-level* spans (call sites ask :meth:`should_sample`
    once per request) with a deterministic counter-based sampler —
    batch/lifecycle spans are low-rate and always recorded while
    enabled.
    """

    def __init__(self, *, enabled: bool = False, sample_rate: float = 1.0,
                 clock=time.monotonic, maxlen: int = 65536):
        self.enabled = False
        self.sample_rate = float(sample_rate)
        self.clock = clock
        self.events: deque[Span] = deque(maxlen=maxlen)
        self._sid = 0
        self._stack: list[int] = []      # open context-manager span ids
        self._sample_acc = 0.0
        self._t_sync = 0.0               # clock reading of the last clock.sync
        self._t_gc = 0.0                 # start of the collector pass under way
        # kwargs (parent=, tid=) that spans recorded inside a children()
        # block carry: ``add_span(..., **tracer.scope)``
        self.scope: dict = {}
        if enabled:
            self.enable()

    # ------------------------------------------------------------- control
    def enable(self, sample_rate: float | None = None) -> "Tracer":
        self.enabled = True
        if sample_rate is not None:
            self.sample_rate = float(sample_rate)
        _hook_process(self)
        self._sync()
        return self

    def disable(self) -> "Tracer":
        self.enabled = False
        _unhook_process(self)
        return self

    def clear(self) -> None:
        self.events.clear()
        self._stack.clear()
        self._sample_acc = 0.0
        if self.enabled:
            self._sync()

    def should_sample(self) -> bool:
        """Deterministic rate limiter for per-request spans: fires on the
        calls where the accumulated rate crosses an integer (rate 1.0 →
        always, 0.5 → every other, 0 → never)."""
        if not self.enabled:
            return False
        self._sample_acc += self.sample_rate
        if self._sample_acc >= 1.0:
            self._sample_acc -= 1.0
            return True
        return False

    # ----------------------------------------------------------- recording
    def _next_sid(self) -> int:
        self._sid += 1
        return self._sid

    def new_id(self) -> int:
        """Reserve a span id for a parent recorded after its children
        (pass it back as ``add_span(..., sid=...)``)."""
        return self._next_sid()

    @contextmanager
    def children(self, parent: int, tid: int):
        """A block in which :attr:`scope` is ``{"parent": parent, "tid":
        tid}``: code the caller calls (a collection's ``search``) records
        ``add_span(..., **tracer.scope)`` and so becomes the caller's
        child on its lane without knowing either.  Outside any block
        ``scope`` is empty: scheduler lane, no parent."""
        prev, self.scope = self.scope, {"parent": parent, "tid": tid}
        try:
            yield
        finally:
            self.scope = prev

    def _sync(self) -> None:
        """Record a ``clock.sync`` instant: this tracer's clock beside
        ``time.time_ns()``, read between two clock reads."""
        t0 = self.clock()
        wall = time.time_ns()
        t1 = self.clock()
        self._t_sync = t1
        self.events.append(Span(
            "clock.sync", "clock", (t0 + t1) / 2, 0.0, TID_SCHEDULER,
            self._next_sid(), None, {"wall_ns": wall}, ph="i",
        ))

    def add_span(self, name: str, t_start: float, t_end: float, *,
                 cat: str = "host", tid: int = TID_SCHEDULER,
                 parent: int | None = None, sid: int | None = None,
                 **args) -> int | None:
        """Record a completed interval measured by the caller (the
        two-phase form the overlapped scheduler needs); returns its span
        id (``None`` when disabled).  ``sid`` is an id reserved with
        :meth:`new_id`; ``parent`` the enclosing span's id.  Timestamps
        must come from the same clock family as ``self.clock`` so the
        timeline stays coherent."""
        if not self.enabled:
            return None
        if self.clock() - self._t_sync >= SYNC_EVERY_S:
            self._sync()
        if sid is None:
            sid = self._next_sid()
        self.events.append(Span(
            name, cat, t_start, max(t_end - t_start, 0.0), tid, sid, parent,
            args,
        ))
        return sid

    def instant(self, name: str, *, cat: str = "host",
                tid: int = TID_SCHEDULER, t: float | None = None,
                **args) -> None:
        """A point event (quota rejection, request completion, breach)."""
        if not self.enabled:
            return
        ts = self.clock() if t is None else t
        self.events.append(Span(
            name, cat, ts, 0.0, tid, self._next_sid(), None, args, ph="i",
        ))

    @contextmanager
    def span(self, name: str, *, cat: str = "host",
             tid: int = TID_LIFECYCLE, **args):
        """Context-managed span for synchronous work (lifecycle
        mutations, benchmark phases).  Nesting is tracked: the recorded
        span carries the enclosing span's id as ``parent``."""
        if not self.enabled:
            yield _NOP
            return
        sid = self._next_sid()
        parent = self._stack[-1] if self._stack else None
        self._stack.append(sid)
        live = _LiveSpan(dict(args))
        t0 = self.clock()
        try:
            yield live
        finally:
            t1 = self.clock()
            self._stack.pop()
            self.events.append(
                Span(name, cat, t0, t1 - t0, tid, sid, parent, live.args)
            )

    # ---------------------------------------------------- process records
    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t_gc = self.clock()
        else:
            self.add_span("host.gc", self._t_gc, self.clock(), cat="runtime",
                          generation=info["generation"],
                          collected=info["collected"])

    def _on_compile(self, seconds: float) -> None:
        t1 = self.clock()
        self.add_span("jit.compile", t1 - seconds, t1, cat="runtime",
                      seconds=seconds)

    # ------------------------------------------------------------- exports
    def export_jsonl(self, path: str) -> int:
        """One span per line, full record (ts/dur in seconds), the
        ``clock.sync`` instants that carry the offset to the profiler's
        clock included; returns the number of spans written."""
        events = sorted(self.events, key=lambda s: s.ts)
        with open(path, "w") as f:
            for s in events:
                f.write(json.dumps(s.to_dict()) + "\n")
        return len(events)

    def to_trace_events(self) -> list[dict]:
        """Chrome ``trace_event`` records (ts/dur in microseconds).
        ``cat == "request"`` spans become async begin/end pairs keyed on
        the span id (or ``args["uid"]`` when present) so overlapping
        queued requests render side by side; a ``request.done`` instant
        becomes the pair from its ``submitted`` to its completion; other
        instants become ``ph: "i"``; everything else is a complete
        ``ph: "X"`` slice on its lane."""
        out = []
        for tid, label in sorted(_TRACK_NAMES.items()):
            out.append({
                "ph": "M", "name": "thread_name", "pid": 0, "tid": tid,
                "args": {"name": label},
            })
        ring_tids = sorted({
            s.tid for s in self.events
            if TID_RING0 <= s.tid < TID_LIFECYCLE
        })
        for tid in ring_tids:
            out.append({
                "ph": "M", "name": "thread_name", "pid": 0, "tid": tid,
                "args": {"name": f"ring slot {tid - TID_RING0}"},
            })
        for s in sorted(self.events, key=lambda x: x.ts):
            ts_us = s.ts * 1e6
            base = {"name": s.name, "cat": s.cat, "pid": 0, "tid": s.tid,
                    "args": s.args}
            if s.name == "request.done":
                ev_id = str(s.args["uid"])
                out.append({**base, "ph": "b", "id": ev_id,
                            "ts": s.args["submitted"] * 1e6})
                out.append({**base, "ph": "e", "id": ev_id, "ts": ts_us})
            elif s.ph == "i":
                out.append({**base, "ph": "i", "ts": ts_us, "s": "t"})
            elif s.cat == "request":
                ev_id = str(s.args.get("uid", s.sid))
                out.append({**base, "ph": "b", "id": ev_id, "ts": ts_us})
                out.append({**base, "ph": "e", "id": ev_id,
                            "ts": ts_us + s.dur * 1e6})
            else:
                out.append({**base, "ph": "X", "ts": ts_us,
                            "dur": s.dur * 1e6})
        return out

    def export_perfetto(self, path: str) -> int:
        """Write the Chrome/Perfetto ``trace_event`` JSON; returns the
        number of trace events (metadata included).  ``otherData``
        carries ``wall_minus_clock_ns``: add it to ``ts * 1e3`` to put an
        event on ``time.time_ns()``, the clock of a ``jax.profiler``
        trace of the same process."""
        events = self.to_trace_events()
        offset = wall_offset_ns(self.events)
        if offset is None:
            offset = time.time_ns() - self.clock() * 1e9
        with open(path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms",
                       "otherData": {"wall_minus_clock_ns": offset}}, f)
        return len(events)


def wall_offset_ns(spans) -> float | None:
    """``time.time_ns()`` minus the tracer clock in ns, from the last
    ``clock.sync`` among ``spans`` (``None`` when there is none)."""
    last = None
    for s in spans:
        if s.name == "clock.sync" and (last is None or s.ts >= last.ts):
            last = s
    return None if last is None else last.args["wall_ns"] - last.ts * 1e9


# ----------------------------------------------------- process-wide hooks
# The Python collector and the XLA compiler are process-wide: one
# collector callback and one jax.monitoring listener fan out to every
# enabled tracer (held weakly, so a dropped tracer stops recording) and,
# for compiles, to every registry that counts them.  The collector
# callback is installed only while some tracer is enabled.
_ENABLED: "weakref.WeakSet[Tracer]" = weakref.WeakSet()
_COMPILE_REGISTRIES: "weakref.WeakSet" = weakref.WeakSet()
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_listening = False


def _gc_callback(phase: str, info: dict) -> None:
    for tracer in list(_ENABLED):
        tracer._on_gc(phase, info)


def _compile_listener(event: str, seconds: float, **kw) -> None:
    if event != _COMPILE_EVENT:
        return
    for reg in list(_COMPILE_REGISTRIES):
        reg.counter("repro_jit_compiles_total").inc()
        reg.counter("repro_jit_compile_seconds_total").inc(seconds)
    for tracer in list(_ENABLED):
        tracer._on_compile(seconds)


def _listen_compiles() -> None:
    global _compile_listening
    if not _compile_listening:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_compile_listener)
        _compile_listening = True


def _hook_process(tracer: Tracer) -> None:
    _ENABLED.add(tracer)
    if _gc_callback not in gc.callbacks:
        gc.callbacks.append(_gc_callback)
    _listen_compiles()


def _unhook_process(tracer: Tracer) -> None:
    _ENABLED.discard(tracer)
    if not _ENABLED and _gc_callback in gc.callbacks:
        gc.callbacks.remove(_gc_callback)


def watch_compiles(registry) -> None:
    """Count every XLA backend compile of the process in ``registry``:
    ``repro_jit_compiles_total`` and ``repro_jit_compile_seconds_total``
    (always on; the listener runs only when something compiles)."""
    registry.counter("repro_jit_compiles_total",
                     "XLA backend compiles in this process")
    registry.counter("repro_jit_compile_seconds_total",
                     "Seconds spent in XLA backend compiles")
    _COMPILE_REGISTRIES.add(registry)
    _listen_compiles()


# The process-wide tracer: lifecycle spans of collections attached to no
# service, and any service built without an explicit tracer, record
# here, so one export shows mutations and serving on a single timeline.
_global_tracer = Tracer(enabled=False)


def get_tracer() -> Tracer:
    return _global_tracer
