"""The traffic generator and the loops that drive the service: seeded
draws repeat, and latency runs from the due time."""

import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, traffic  # noqa: E402
from bench.tests import tiny  # noqa: E402

CLOSED = traffic.load(ROOT / "bench" / "traffic" / "closed64-fresh.json")
OPEN = dict(tiny.OPEN_ZIPF, queries=dict(tiny.OPEN_ZIPF["queries"], pool=10_000))
SEED = 2**33 + 12345  # wider than 32 bits: a seed may be


def test_due_times_repeat_for_a_seed_and_offer_a_fixed_count():
    a = traffic.due_times(OPEN, SEED, 10.0)
    b = traffic.due_times(OPEN, SEED, 10.0)
    c = traffic.due_times(OPEN, SEED + 1, 10.0)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.size == c.size == round(OPEN["rate_qps"] * 10.0)
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 10.0
    gaps = np.diff(a)  # Poisson: exponential gaps of mean 1 / rate
    assert abs(gaps.mean() * OPEN["rate_qps"] - 1) < 0.05
    assert abs(np.std(gaps) / gaps.mean() - 1) < 0.1


def test_zipf_draws_repeat_for_a_seed_and_are_skewed():
    a = traffic.QueryOrder(OPEN, SEED).take(50_000)
    o = traffic.QueryOrder(OPEN, SEED)
    b = np.concatenate([o.take(1), o.take(49_999)])
    assert np.array_equal(a, b)
    assert not np.array_equal(a, traffic.QueryOrder(OPEN, SEED + 1).take(50_000))
    pool = OPEN["queries"]["pool"]
    assert a.min() >= 0 and a.max() < pool
    counts = np.bincount(a, minlength=pool)
    assert counts[0] > counts[1] > counts[10] > counts[1000]
    # rank 1 of Zipf(0.99) over 10,000 takes 1 / H(10,000; 0.99) of draws
    w = 1 / np.arange(1, pool + 1) ** OPEN["queries"]["zipf_s"]
    assert abs(counts[0] / a.size - w[0] / w.sum()) < 0.01


def test_sequential_order_walks_the_pool():
    o = traffic.QueryOrder(CLOSED, SEED)
    rows = np.concatenate([o.take(3), o.take(CLOSED["queries"]["pool"])])
    assert list(rows[:5]) == [0, 1, 2, 3, 4]
    assert rows[-1] == 2  # wraps after the pool


def test_tenants_take_turns():
    names = sorted(CLOSED["tenants"])
    assert [traffic.tenant(CLOSED, i) for i in range(4)] == names * 2


class FakeClock:
    def __init__(self, step=1e-3):
        self.t, self.step = 100.0, step

    def __call__(self):
        self.t += self.step
        return self.t


class Ticket:
    def __init__(self, t):
        self.submitted, self.done, self.latency_ms = t, False, None
        self.error, self.degraded, self.cached = None, False, False
        self.ids, self.dists, self.payload = np.arange(3), np.ones(3, np.float32), None


class FakeService:
    """Completes each request ``delay`` seconds after its submit, at the
    first ``step`` after that."""

    def __init__(self, clock, delay):
        self.clock, self.delay, self.open = clock, delay, []

    def submit(self, name, q, tenant):
        t = Ticket(self.clock())
        self.open.append(t)
        return t

    def pending(self):
        return 0  # every request is in flight at once

    def step(self):
        now = self.clock()
        for t in [t for t in self.open if now - t.submitted >= self.delay]:
            t.done, t.latency_ms = True, (now - t.submitted) * 1e3
            self.open.remove(t)


def run_closed(seed):
    clock = FakeClock()
    svc = FakeService(clock, delay=0.02)
    pool = np.zeros((CLOSED["queries"]["pool"], 4), np.float32)
    mix = dict(CLOSED, clients=4)
    win = harness.run_closed(svc, "c", mix, pool, traffic.QueryOrder(mix, seed), 1.0, clock,
                             harness.Ledger(3, cap=8))
    return win, svc


def test_closed_loop_accounting_repeats_and_times_from_the_submit():
    (a, svc), (b, _) = run_closed(SEED), run_closed(SEED)
    assert len(a.open) == 4  # one request a client still open at the close
    svc.step()  # what the harness's flush does, then the rest is read
    a.finish()
    b.finish()
    led = a.ledger
    assert led.n == b.ledger.n > 4 * 10
    order = np.argsort(led.view("seq"))
    assert list(led.view("row")[order]) == list(range(led.n))  # pool rows in order
    assert np.array_equal(led.view("row")[order], b.ledger.view("row")[np.argsort(b.ledger.view("seq"))])
    done_at, lat = harness.latencies(a)
    finished = np.isfinite(done_at)
    assert np.all(lat[finished] >= 20.0 - 1e-9)  # the service's 20 ms, from the submit
    assert np.sum(~finished) <= 4
    assert led.view("ids").shape == (led.n, 3)


def test_open_loop_times_latency_from_the_due_time():
    """A generator that falls behind sends late; the wait counts."""
    clock = FakeClock(step=0.05)  # the loop runs 20 times a second: it is late
    svc = FakeService(clock, delay=0.01)
    pool = np.zeros((OPEN["queries"]["pool"], 4), np.float32)
    mix = dict(OPEN, rate_qps=100.0)
    win = harness.run_open(svc, "o", mix, pool, traffic.QueryOrder(mix, SEED), 1.0,
                           SEED, clock, harness.Ledger(3, cap=8))
    while win.open:  # the harness's flush
        svc.step()
        win.read_done()
    win.finish()
    assert win.ledger.n == 100
    due = traffic.due_times(mix, SEED, 1.0) + win.t_open
    order = np.argsort(win.ledger.view("seq"))
    assert np.allclose(win.ledger.view("due")[order], due)
    done_at, lat = harness.latencies(win)
    assert np.allclose(lat, (done_at - due) * 1e3)
    assert np.all(lat >= 10.0 - 1e-9)
    assert max(win.lateness) > 0.0
    # the wait before a late send is in the latency
    late = np.argmax(win.lateness)
    assert lat[late] >= win.lateness[late] * 1e3 + 10.0 - 1e-6


def test_bench_json_traffic_names_are_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
