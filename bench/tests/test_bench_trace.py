"""The trace reduction against a trace recorded on the chip: device 0's
XLA ops over four search batches of ``sift1m.closed64`` on one TPU v5
lite, trimmed to those batches (``data/sift1m_closed64_v5e.*``)."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, trace_reduce  # noqa: E402

DATA = ROOT / "bench" / "tests" / "data"
SIDE = json.loads((DATA / "sift1m_closed64_v5e.json").read_text())
TRACE = str(DATA / "sift1m_closed64_v5e.xplane.pb")


@pytest.fixture(scope="module")
def dt():
    return trace_reduce.load(TRACE, t0_ns=SIDE["t0_ns"], t1_ns=SIDE["t1_ns"], n_devices=1)


def test_device_plane_and_scopes_are_found(dt):
    assert dt.found == 1 and dt.ops
    assert dt.scope_s("dblsh.select") > 0
    assert dt.scope_s("dblsh.verify") > 0
    assert dt.scope_s("dblsh.project") > 0
    assert dt.kernel_s("fused_window_search") > 0
    assert dt.collective_s() == 0  # one chip: no exchange
    assert dt.kernel_s("fused_window_search") <= dt.scope_s("dblsh.verify")


def test_busy_and_idle_are_shares_of_the_window(dt):
    assert 0 < dt.busy_s <= dt.window_s
    idle = sum(e - s for s, e in dt.gaps) / 1e9
    assert abs(idle + dt.busy_s - dt.window_s) < 1e-6
    assert all(s >= SIDE["t0_ns"] and e <= SIDE["t1_ns"] for s, e in dt.gaps)


def test_nothing_outside_the_window_is_read(dt):
    half = trace_reduce.load(TRACE, t0_ns=SIDE["t0_ns"],
                             t1_ns=(SIDE["t0_ns"] + SIDE["t1_ns"]) / 2, n_devices=1)
    assert half.window_s == pytest.approx(dt.window_s / 2, abs=1e-6)  # ns since the epoch in float64
    assert 0 < half.busy_s < dt.busy_s
    assert all(o.start_ns + o.dur_ns <= (SIDE["t0_ns"] + SIDE["t1_ns"]) / 2 + 1 for o in half.ops)


def _ctx(dt):
    cfg = json.loads((ROOT / "bench" / "configs" / "sift1m-inline.json").read_text())
    batches = [SimpleNamespace(name="batch.issue", dur=0.0, args={"shape": SIDE["batch_shape"]})
               for _ in range(SIDE["batches"])]
    return harness.LayerContext(cfg, {}, dt.window_s, batches, dt,
                                harness.peaks_for("TPU v5 lite"), harness.work_module)


def _read(name, ctx):
    return harness.load_module(ROOT / "bench" / "layer_metrics" / f"{name}.py").read(ctx)


def test_per_batch_times_fit_in_the_window(dt):
    ctx = _ctx(dt)
    select, verify = _read("select_ms_per_batch", ctx), _read("verify_ms_per_batch", ctx)
    assert select > 0 and verify > 0
    assert (select + verify) * SIDE["batches"] / 1e3 <= dt.window_s
    assert _read("collective_ms_per_batch", ctx) is None
    idle = _read("device_idle_share.closed", ctx)
    assert 0 <= idle <= 1


def test_roofline_share_is_a_share(dt):
    share = _read("fused_window_search_roofline", _ctx(dt))
    assert 0 < share <= 100


def test_breakdown_names_ops_and_gaps(dt):
    b = trace_reduce.breakdown(dt, [], 0.0)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][1] >= b["device_ops"][-1][1]
    assert all(label == "host idle" for label, _ in b["idle_gaps"])
