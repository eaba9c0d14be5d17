"""From a profiler trace to what the per-layer readers read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote, through
the XPlane protocol buffer (``xplane_pb2``, generated from TSL's
``xplane.proto``), and keeps, for each device, the operations of its
``XLA Ops`` line that ran inside the measured window: their name, start,
duration, category and ``tf_op``, the path of ``jax.named_scope`` labels
and jitted functions the operation came from (``jit(search_batch_fixed)/
dblsh.select/...``).  From those it takes

* busy time: the union of a device's operation intervals, clipped to
  the window, averaged over the devices;
* idle gaps: the stretches of the window in which device 0 ran nothing;
* scope time and kernel time: the summed durations of the operations
  whose ``tf_op`` names a scope, or whose name is the kernel's.

Times in an XSpace are offsets from its ``profile_start_time`` (the
"Task Environment" plane): a line's ``timestamp_ns`` plus an event's
``offset_ps``.
"""

from __future__ import annotations

import glob
import importlib.util
import os
import re
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(r"all-gather|all-reduce|all-to-all|collective-permute|reduce-scatter", re.I)


def _xplane_pb2():
    spec = importlib.util.spec_from_file_location(
        "bench_xplane_pb2", Path(__file__).with_name("xplane_pb2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Op:
    device: int
    name: str         # the op's short name, e.g. ``fused_window_search.1``
    start_ns: float   # absolute, nanoseconds since the epoch
    dur_ns: float
    tf_op: str        # scope path, e.g. ``jit(search_batch_fixed)/dblsh.select/neg:``
    category: str     # XLA's hlo_category, e.g. ``custom-call``


@dataclass
class DeviceTrace:
    window_s: float
    busy_s: float                      # union of op intervals, mean over devices
    n_devices: int
    found: int = 0                     # device planes the trace holds
    ops: list = field(default_factory=list)
    gaps: list = field(default_factory=list)  # (start_ns, end_ns) idle on device 0

    def time_s(self, pred) -> float:
        """Summed duration of the ops ``pred`` accepts, mean over devices."""
        return sum(o.dur_ns for o in self.ops if pred(o)) / 1e9 / self.n_devices

    def scope_s(self, scope: str) -> float:
        return self.time_s(lambda o: f"/{scope}/" in o.tf_op)

    def kernel_s(self, kernel: str) -> float:
        return self.time_s(lambda o: o.category == "custom-call"
                           and o.name.split(".")[0] == kernel)

    def collective_s(self) -> float:
        # XLA names the category (all-reduce for a psum or pmax, all-gather,
        # ...); the op's own name is the jax primitive's (psum.7, pmax.7)
        return self.time_s(lambda o: bool(COLLECTIVE.search(o.category)
                                          or COLLECTIVE.search(o.name)))


def find_trace(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def read_space(path: str):
    space = _xplane_pb2().XSpace()
    with open(find_trace(path), "rb") as f:
        space.ParseFromString(f.read())
    return space


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _meta_stats(plane, md) -> dict:
    names = {k: v.name for k, v in plane.stat_metadata.items()}
    out = {}
    for st in md.stats:
        if st.HasField("str_value"):
            out[names.get(st.metadata_id)] = st.str_value
        elif st.HasField("ref_value"):
            out[names.get(st.metadata_id)] = names.get(st.ref_value, "")
    return out


def load(path: str, *, t0_ns: float, t1_ns: float, n_devices: int) -> DeviceTrace:
    """The ops of devices ``0 .. n_devices - 1`` inside [t0_ns, t1_ns]
    (absolute nanoseconds since the epoch)."""
    space = read_space(path)
    start = 0
    for plane in space.planes:
        if plane.name == "Task Environment":
            names = {k: v.name for k, v in plane.stat_metadata.items()}
            for st in plane.stats:
                if names.get(st.metadata_id) == "profile_start_time":
                    start = st.uint64_value or st.int64_value
    ops, found = [], 0
    for plane in space.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) >= n_devices:
            continue
        dev = int(m.group(1))
        found += 1
        meta = {}
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            base = start + line.timestamp_ns
            for ev in line.events:
                s = base + ev.offset_ps / 1e3
                e = s + ev.duration_ps / 1e3
                if e <= t0_ns or s >= t1_ns:
                    continue
                if ev.metadata_id not in meta:
                    md = plane.event_metadata[ev.metadata_id]
                    st = _meta_stats(plane, md)
                    meta[ev.metadata_id] = (md.display_name or md.name,
                                            st.get("tf_op", ""), st.get("hlo_category", ""))
                name, tf_op, cat = meta[ev.metadata_id]
                s, e = max(s, t0_ns), min(e, t1_ns)
                ops.append(Op(dev, name, s, e - s, tf_op, cat))
    busy = []
    for dev in range(n_devices):
        u = _union([(o.start_ns, o.start_ns + o.dur_ns) for o in ops if o.device == dev])
        busy.append(sum(e - s for s, e in u))
    u0 = _union([(o.start_ns, o.start_ns + o.dur_ns) for o in ops if o.device == 0])
    edges = [t0_ns] + [x for iv in u0 for x in iv] + [t1_ns]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    return DeviceTrace(window_s=(t1_ns - t0_ns) / 1e9,
                       busy_s=float(np.mean(busy)) / 1e9 if busy else 0.0,
                       n_devices=n_devices, found=found, ops=ops, gaps=gaps)


def breakdown(dt: DeviceTrace, spans: list, wall_minus_mono_ns: float, top: int = 10) -> dict:
    """The device ops that took most time, and the longest idle gaps of
    device 0 named by the program's host span that covers most of each
    ("host idle" where none does)."""
    by_name: dict[str, float] = {}
    for o in dt.ops:
        by_name[o.name] = by_name.get(o.name, 0.0) + o.dur_ns / 1e9 / dt.n_devices
    device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    host = [(s.name, s.ts * 1e9 + wall_minus_mono_ns, (s.ts + s.dur) * 1e9 + wall_minus_mono_ns)
            for s in spans if s.dur > 0]
    gaps = sorted(dt.gaps, key=lambda g: g[0] - g[1])[:top]
    named = []
    for g0, g1 in gaps:
        cover: dict[str, float] = {}
        for name, s, e in host:
            ov = min(e, g1) - max(s, g0)
            if ov > 0:
                cover[name] = cover.get(name, 0.0) + ov
        label = max(cover, key=cover.get) if cover else "host idle"
        named.append([label, (g1 - g0) / 1e9])
    return {"device_ops": [[k, v] for k, v in device_ops], "idle_gaps": named}
