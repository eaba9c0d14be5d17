"""repro.resilience test harness.

Five suites over the fault-injection / degradation / recovery layer:

* **FaultPlan units** — deterministic firing windows (``at`` / ``count``
  / ctx match), the installed-plan lifecycle (``active`` nesting,
  no-op default), delay sites through an injectable sleep.
* **Checkpointer integrity** — crc32 verify-on-restore raising typed
  :class:`CorruptSnapshot` (naming step + file), fallback to the newest
  *verified* step, garbled-manifest ``read_meta``, stranded-``LATEST``
  recovery, GC skipping a step a concurrent restore is mid-read on,
  orphan ``.tmp`` salvage vs torn-tmp GC, ``save_async`` error
  surfacing at ``wait()``, and v1 (pre-checksum) manifest back-compat.
* **Crash consistency (property)** — kill the snapshot writer at every
  fault site in the snapshot lane (hypothesis over sites × torn byte
  offsets); ``restore_collection`` must always land on a committed
  snapshot whose search results are bit-equal to one the writer
  actually reached, and the directory must sweep clean of tmp dirs.
* **Degraded serving** — ``deadline_ms`` expiry (typed
  ``DeadlineExceeded``), deadline re-planning through a measured
  calibration table (flagged ``degraded``), transient dispatch retry
  with capped backoff (bit-equal results), persistent dispatch failure
  terminating every ticket typed (never hung), and the brownout ladder
  (escalate on SLO breach / heal on clean windows / shed by quota
  weight) — plus the acceptance pin: with no faults installed (or an
  installed-but-empty plan) the service is bit-equal to the plain
  stack, across the engine matrix.
* **Stragglers** — the EWMA monitor (shared with
  ``runtime.fault_tolerance``, re-export identity pinned), its service
  wiring (slow batch flagged into ``stats()['straggler_batches']``),
  and the ``shard.straggle`` site firing in sharded search.

Engine matrix: ``REPRO_STORE_TEST_ENGINES`` (default ``jnp``), same
convention as the scheduler harness.
"""

import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hypothesis import given, settings
from hypothesis import strategies as st
from repro.checkpoint import Checkpointer, CorruptSnapshot
from repro.compat import make_mesh
from repro.core import DBLSHParams
from repro.data import make_clustered, normalize_scale
from repro.obs.slo import SLOWatch
from repro.resilience import (
    SNAPSHOT_CRASH_STAGES,
    BrownoutController,
    FaultPlan,
    SimulatedCrash,
    StragglerMonitor,
    faults,
)
from repro.store import (
    BrownoutShed,
    Collection,
    DeadlineExceeded,
    DispatchFailed,
    StoreService,
    restore_collection,
)
from repro.tune.planner import ScheduleTable

ENGINES = os.environ.get("REPRO_STORE_TEST_ENGINES", "jnp").replace(",", " ").split()


class FakeClock:
    """Injectable monotonic clock: time only moves when told to."""

    def __init__(self, start: float = 0.0):
        self.now = start

    def __call__(self) -> float:
        return self.now

    def advance(self, seconds: float) -> float:
        self.now += seconds
        return self.now


@pytest.fixture(scope="module")
def setup():
    kd, kb = jax.random.split(jax.random.key(31))
    allpts = make_clustered(kd, 280, 12, n_clusters=6, spread=0.02)
    data, queries = allpts[:240], allpts[240:]
    data, queries, _ = normalize_scale(data, queries)
    return np.asarray(data), np.asarray(queries), kb


@pytest.fixture(scope="module")
def col(setup):
    data, _, kb = setup
    params = DBLSHParams.derive(
        n=240, d=12, c=1.5, w0=3.6, t=16, k=10, inline_vectors=True
    )
    return Collection.create("res", kb, data, params=params)


def _service(col, *, engine="jnp", depth=2, clock=None, **kw):
    kw.setdefault("batch_shapes", (1, 4, 8))
    kw.setdefault("max_wait_ms", 1e9)
    kw.setdefault("cache_size", 0)
    svc = StoreService(
        default_k=10, r0=0.5, steps=6, engine=engine,
        interpret=True if engine != "jnp" else None,
        inflight_depth=depth,
        **({"clock": clock} if clock is not None else {}),
        **kw,
    )
    svc.attach(col)
    return svc


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    """Every test starts and ends with no installed fault plan."""
    faults.uninstall()
    yield
    faults.uninstall()


# ---------------------------------------------------------------------------
# FaultPlan units
# ---------------------------------------------------------------------------


class TestFaultPlan:
    def test_noop_without_install(self):
        assert faults.fire("dispatch.raise") is None
        assert faults.fire("snapshot.write.torn", file="arr_0.npy") is None

    def test_at_count_window(self):
        plan = FaultPlan().add("dispatch.raise", at=2, count=2)
        with faults.active(plan):
            faults.fire("dispatch.raise")  # hit 0: before window
            faults.fire("dispatch.raise")  # hit 1
            for _ in range(2):             # hits 2, 3: inside
                with pytest.raises(faults.FaultError):
                    faults.fire("dispatch.raise")
            faults.fire("dispatch.raise")  # hit 4: past window
        assert len(plan.fired) == 2

    def test_ctx_match_filters_hits(self):
        plan = FaultPlan().add(
            "snapshot.write.torn", arg=7, file="arr_1.npy", count=math.inf
        )
        with faults.active(plan):
            assert faults.fire("snapshot.write.torn", file="arr_0.npy") is None
            assert faults.fire("snapshot.write.torn", file="arr_1.npy") == 7
        # non-matching hits never consumed the window
        assert [c["file"] for _, c in plan.fired] == ["arr_1.npy"]

    def test_transient_flag_travels(self):
        plan = FaultPlan().add("dispatch.raise", transient=False)
        with faults.active(plan), pytest.raises(faults.FaultError) as ei:
            faults.fire("dispatch.raise")
        assert ei.value.transient is False
        assert isinstance(SimulatedCrash("x"), faults.FaultError)
        assert SimulatedCrash("x").transient is False

    def test_delay_site_uses_injected_sleep_and_scale(self):
        slept = []
        plan = FaultPlan(sleep=slept.append).add(
            "dispatch.delay_ms", arg=20.0, count=math.inf
        )
        with faults.active(plan):
            assert faults.fire("dispatch.delay_ms", scale=3) == 60.0
        assert slept == [0.06]

    def test_active_nesting_restores_previous(self):
        outer, inner = FaultPlan(), FaultPlan()
        with faults.active(outer):
            with faults.active(inner):
                assert faults._ACTIVE is inner
            assert faults._ACTIVE is outer
        assert faults._ACTIVE is None

    def test_reset_rewinds_counters(self):
        plan = FaultPlan().add("dispatch.raise")
        with faults.active(plan):
            with pytest.raises(faults.FaultError):
                faults.fire("dispatch.raise")
            faults.fire("dispatch.raise")  # window spent
            plan.reset()
            with pytest.raises(faults.FaultError):
                faults.fire("dispatch.raise")


# ---------------------------------------------------------------------------
# Checkpointer integrity + recovery
# ---------------------------------------------------------------------------


def _tree(seed: int):
    rng = np.random.default_rng(seed)
    return {
        "a": rng.standard_normal(32).astype(np.float32),
        "b": rng.integers(0, 100, (4, 4)),
    }


class TestCheckpointerIntegrity:
    def test_crc_roundtrip_and_manifest_v2(self, tmp_path):
        ck = Checkpointer(str(tmp_path))
        ck.save(1, _tree(1), meta={"k": 1})
        manifest = ck._load_manifest(1)
        assert manifest["manifest_version"] == 2
        assert all("crc32" in spec for spec in manifest["leaves"])
        tree, meta = ck.restore()
        np.testing.assert_array_equal(tree["a"], _tree(1)["a"])
        assert meta == {"k": 1}

    def test_corrupt_leaf_raises_typed_and_falls_back(self, tmp_path):
        ck = Checkpointer(str(tmp_path))
        ck.save(1, _tree(1), meta={"k": 1})
        ck.save(2, _tree(2), meta={"k": 2})
        p = tmp_path / "step_00000002" / "arr_0.npy"
        blob = p.read_bytes()
        p.write_bytes(blob[:-3] + b"zzz")
        # explicit step: strict, typed, names the step and file
        with pytest.raises(CorruptSnapshot) as ei:
            ck.restore(step=2)
        assert ei.value.step == 2 and ei.value.file == "arr_0.npy"
        # step=None: falls back to the newest step that verifies
        tree, meta = ck.restore()
        assert meta == {"k": 1}
        np.testing.assert_array_equal(tree["a"], _tree(1)["a"])

    def test_injected_read_corruption_caught_by_crc(self, tmp_path):
        ck = Checkpointer(str(tmp_path))
        ck.save(1, _tree(1), meta={"k": 1})
        ck.save(2, _tree(2), meta={"k": 2})
        plan = FaultPlan().add(
            "snapshot.read.corrupt", arg=10, count=math.inf, step=2
        )
        with faults.active(plan):
            tree, meta = ck.restore()
        assert meta == {"k": 1}  # step 2's flipped byte failed its crc
        assert plan.fired

    def test_garbled_manifest_read_meta_typed(self, tmp_path):
        ck = Checkpointer(str(tmp_path))
        ck.save(3, _tree(3), meta={"k": 3})
        (tmp_path / "step_00000003" / "manifest.json").write_text("{tor")
        with pytest.raises(CorruptSnapshot) as ei:
            ck.read_meta(3)
        assert ei.value.step == 3 and "manifest.json" in ei.value.file

    def test_stranded_latest_falls_back(self, tmp_path):
        ck = Checkpointer(str(tmp_path))
        ck.save(1, _tree(1), meta={"k": 1})
        ck.save(2, _tree(2), meta={"k": 2})
        # LATEST names a step whose dir is gone (crash-between-rename-
        # and-LATEST's mirror image: GC'd dir, stale pointer)
        (tmp_path / "LATEST").write_text("7")
        assert ck.latest_step() == 2
        _, meta = ck.restore()
        assert meta == {"k": 2}
        # torn LATEST content
        (tmp_path / "LATEST").write_text("st")
        assert ck.latest_step() == 2
        _, meta = ck.restore()
        assert meta == {"k": 2}

    def test_missing_latest_file_falls_back(self, tmp_path):
        ck = Checkpointer(str(tmp_path))
        ck.save(1, _tree(1), meta={"k": 1})
        (tmp_path / "LATEST").unlink()
        assert ck.latest_step() == 1
        _, meta = ck.restore()
        assert meta == {"k": 1}

    def test_gc_skips_step_mid_restore(self, tmp_path):
        ck = Checkpointer(str(tmp_path), keep=1)
        ck.save(1, _tree(1), meta={"k": 1})
        with ck._reading_lock:
            ck._reading.add(1)  # a concurrent restore() holds step 1
        ck.save(2, _tree(2), meta={"k": 2})
        assert (tmp_path / "step_00000001").exists()
        with ck._reading_lock:
            ck._reading.discard(1)
        ck.save(3, _tree(3), meta={"k": 3})
        assert not (tmp_path / "step_00000001").exists()

    def test_tmp_salvage_and_torn_tmp_gc(self, tmp_path):
        ck = Checkpointer(str(tmp_path))
        ck.save(1, _tree(1), meta={"k": 1})
        # crash after the tmp dir is complete but before the rename:
        # the next Checkpointer salvages it into a real step
        plan = FaultPlan().add("snapshot.write.crash", stage="pre_rename")
        with faults.active(plan), pytest.raises(SimulatedCrash):
            ck.save(2, _tree(2), meta={"k": 2})
        ck2 = Checkpointer(str(tmp_path))
        assert not [n for n in os.listdir(tmp_path) if ".tmp." in n]
        _, meta = ck2.restore()
        assert meta == {"k": 2}
        # a torn leaf leaves an unverifiable tmp: swept, not salvaged
        plan = FaultPlan().add("snapshot.write.torn", file="arr_0.npy", arg=9)
        with faults.active(plan), pytest.raises(SimulatedCrash):
            ck2.save(3, _tree(3), meta={"k": 3})
        ck3 = Checkpointer(str(tmp_path))
        assert not [n for n in os.listdir(tmp_path) if ".tmp" in n]
        _, meta = ck3.restore()
        assert meta == {"k": 2}

    def test_save_async_error_surfaces_at_wait(self, tmp_path):
        ck = Checkpointer(str(tmp_path))
        faults.install(
            FaultPlan().add("snapshot.write.crash", stage="pre_manifest")
        )
        try:
            ck.save_async(1, _tree(1), meta={"k": 1})
            with pytest.raises(SimulatedCrash):
                ck.wait()
        finally:
            faults.uninstall()
        # the recovery path drains without re-raising
        faults.install(
            FaultPlan().add("snapshot.write.crash", stage="pre_manifest")
        )
        try:
            ck.save_async(2, _tree(2), meta={"k": 2})
            ck.wait(reraise=False)
        finally:
            faults.uninstall()

    def test_v1_manifest_backward_compat(self, tmp_path):
        """A PR-7 (pre-checksum) manifest restores: verification is
        simply skipped for leaves with no crc32."""
        import json

        ck = Checkpointer(str(tmp_path))
        ck.save(1, _tree(1), meta={"k": 1})
        mpath = tmp_path / "step_00000001" / "manifest.json"
        manifest = json.loads(mpath.read_text())
        manifest.pop("manifest_version")
        for spec in manifest["leaves"]:
            spec.pop("crc32")
        mpath.write_text(json.dumps(manifest))
        tree, meta = ck.restore()
        assert meta == {"k": 1}
        np.testing.assert_array_equal(tree["a"], _tree(1)["a"])


# ---------------------------------------------------------------------------
# Crash-consistency property: kill the writer at every snapshot-lane site
# ---------------------------------------------------------------------------

# scenario space: 4 crash stages, torn leaf, torn manifest, read corruption
_N_SCENARIOS = len(SNAPSHOT_CRASH_STAGES) + 3


def _snapshot_fault_plan(scenario: int, byte: int, step: int) -> FaultPlan:
    plan = FaultPlan()
    if scenario < len(SNAPSHOT_CRASH_STAGES):
        plan.add(
            "snapshot.write.crash",
            stage=SNAPSHOT_CRASH_STAGES[scenario], step=step,
        )
    elif scenario == len(SNAPSHOT_CRASH_STAGES):
        plan.add("snapshot.write.torn", file="arr_0.npy", arg=byte, step=step)
    elif scenario == len(SNAPSHOT_CRASH_STAGES) + 1:
        plan.add(
            "snapshot.write.torn", file="manifest.json", arg=byte, step=step
        )
    # scenario _N_SCENARIOS-1: no write fault — bit-rot at restore time
    return plan


class TestCrashConsistency:
    @given(
        scenario=st.integers(min_value=0, max_value=_N_SCENARIOS - 1),
        byte=st.integers(min_value=1, max_value=160),
    )
    @settings(max_examples=8, deadline=None)
    def test_restore_always_lands_on_committed_state(
        self, setup, tmp_path_factory, scenario, byte
    ):
        """Whatever site the writer dies at, ``restore_collection`` must
        recover a committed snapshot: its search results are bit-equal
        to the state at one of the snapshots the writer attempted (recall
        parity with a fresh build of that state is implied — the arrays
        are bit-identical), and the directory sweeps clean of tmp dirs."""
        data, queries, kb = setup
        directory = str(tmp_path_factory.mktemp(f"crash_{scenario}_{byte}"))
        params = DBLSHParams.derive(
            n=200, d=12, c=1.5, w0=3.6, t=16, k=10, inline_vectors=True
        )
        col = Collection.create("cc", kb, data[:200], params=params)
        kw = dict(k=10, r0=0.5, steps=6, engine="jnp")
        ref1 = [np.asarray(x) for x in col.search(queries, **kw)]
        step1 = col.snapshot(directory)
        col.add(data[200:240])
        ref2 = [np.asarray(x) for x in col.search(queries, **kw)]

        read_fault = scenario == _N_SCENARIOS - 1
        step2 = step1 + 1
        plan = _snapshot_fault_plan(scenario, byte, step2)
        try:
            with faults.active(plan):
                col.snapshot(directory)
        except SimulatedCrash:
            pass

        if read_fault:
            # the write committed clean; rot step2's bytes at read time
            faults.install(FaultPlan().add(
                "snapshot.read.corrupt", arg=byte, count=math.inf, step=step2,
            ))
        try:
            restored = restore_collection(directory)
        finally:
            faults.uninstall()
        got = [np.asarray(x) for x in restored.search(queries, **kw)]
        matches_1 = all(np.array_equal(g, r) for g, r in zip(got, ref1))
        matches_2 = all(np.array_equal(g, r) for g, r in zip(got, ref2))
        assert matches_1 or matches_2, (
            f"scenario={scenario} byte={byte}: restored state matches "
            "neither attempted snapshot"
        )
        if read_fault:
            assert matches_1  # step2 failed its crc: fell back to step1
        # a fresh Checkpointer sweeps the wreckage
        Checkpointer(directory)
        assert not [n for n in os.listdir(directory) if ".tmp" in n]


# ---------------------------------------------------------------------------
# Degraded serving: deadlines, retries, typed failure, brownout
# ---------------------------------------------------------------------------


def _measured_table() -> ScheduleTable:
    # schedule length j+1 costs 2^j ms; recall climbs toward 1
    return ScheduleTable(
        r0=0.5, c=1.5, k=10,
        recall=(0.55, 0.7, 0.82, 0.9, 0.95, 0.98),
        cost_slots=(8.0, 16.0, 32.0, 64.0, 128.0, 256.0),
        cost_ms=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0),
        n_sample=64,
    )


class TestDeadlines:
    def test_expired_deadline_fails_typed(self, setup, col):
        _, queries, _ = setup
        clk = FakeClock()
        svc = _service(col, clock=clk)
        r = svc.submit("res", queries[0], deadline_ms=10.0)
        clk.advance(0.02)  # 20ms in the queue
        svc.step(force=True)
        assert r.done and isinstance(r.error, DeadlineExceeded)
        assert r.dists is None
        s = svc.stats("res")
        assert s["failed"] == 1 and s["queries"] == 0
        assert svc.tenant_stats("default")["failed"] == 1
        assert svc.pending() == 0 and svc.in_flight() == 0

    def test_deadline_replans_through_measured_table(self, setup, col):
        """A ticket whose remaining budget cannot fit the resolved plan
        is re-planned via LatencyBudget over the measured calibration
        table — shorter schedule, flagged degraded — instead of either
        blowing the deadline or failing outright."""
        _, queries, _ = setup
        clk = FakeClock()
        svc = _service(col, clock=clk)
        old_table = col.calibration
        col.calibration = _measured_table()
        try:
            r = svc.submit("res", queries[0], deadline_ms=10.0)
            assert r.plan.steps == 6  # service default at submit
            clk.advance(0.005)  # 5ms gone -> ~5ms budget -> 3 steps (4ms)
            svc.step(force=True)
        finally:
            col.calibration = old_table
        assert r.done and r.error is None
        assert r.degraded and r.plan.steps == 3
        assert r.dists is not None
        assert svc.stats("res")["degraded"] == 1

    def test_late_completion_flags_degraded(self, setup, col):
        """No calibration: the plan cannot shrink, but a result landing
        past its deadline is still flagged, never silently on-time."""
        _, queries, _ = setup
        clk = FakeClock()
        svc = _service(col, clock=clk, depth=1, max_wait_ms=0.0)
        old_table = col.calibration
        col.calibration = None
        try:
            r = svc.submit("res", queries[0], deadline_ms=10.0)
            svc.step()          # issued within budget
            clk.advance(0.05)   # device "takes" 50ms
            svc.flush()
        finally:
            col.calibration = old_table
        assert r.done and r.error is None and r.degraded
        assert r.plan.steps == 6  # plan untouched — only the flag


class TestDispatchFailure:
    @pytest.mark.parametrize("engine", ENGINES)
    def test_transient_raise_retried_bit_equal(self, setup, col, engine):
        _, queries, _ = setup
        ref = _service(col, engine=engine).serve("res", queries[:4])
        svc = _service(col, engine=engine, sleep=lambda s: None)
        plan = FaultPlan().add("dispatch.raise", count=2, transient=True)
        with faults.active(plan):
            d, i, reqs = svc.serve("res", queries[:4])
        assert len(plan.fired) == 2  # both transient raises were consumed
        np.testing.assert_array_equal(d, ref[0])
        np.testing.assert_array_equal(i, ref[1])
        assert all(r.error is None and not r.degraded for r in reqs)

    def test_backoff_is_capped_exponential(self, setup, col):
        _, queries, _ = setup
        slept = []
        svc = _service(
            col, sleep=slept.append, retry_limit=3,
            retry_backoff_ms=4.0, retry_backoff_cap_ms=10.0,
        )
        plan = FaultPlan().add("dispatch.raise", count=3, transient=True)
        with faults.active(plan):
            svc.serve("res", queries[:1])
        assert slept == [0.004, 0.008, 0.010]  # 4, 8, min(16, cap=10) ms

    def test_persistent_raise_fails_every_ticket_typed(self, setup, col):
        _, queries, _ = setup
        svc = _service(col, sleep=lambda s: None)
        reqs = [svc.submit("res", q) for q in queries[:4]]
        plan = FaultPlan().add(
            "dispatch.raise", count=math.inf, transient=True
        )
        with faults.active(plan):
            svc.flush()
        assert all(r.done for r in reqs)
        assert all(isinstance(r.error, DispatchFailed) for r in reqs)
        assert svc.pending() == 0 and svc.in_flight() == 0
        assert svc.stats("res")["failed"] == 4
        # serve() surfaces the typed error to synchronous callers
        with faults.active(plan.reset()), pytest.raises(DispatchFailed):
            svc.serve("res", queries[:2])

    def test_nontransient_raise_fails_without_retry(self, setup, col):
        _, queries, _ = setup
        slept = []
        svc = _service(col, sleep=slept.append)
        plan = FaultPlan().add("dispatch.raise", transient=False)
        r = svc.submit("res", queries[0])
        with faults.active(plan):
            svc.flush()
        assert isinstance(r.error, DispatchFailed)
        assert slept == []  # no backoff spent on a non-transient error
        assert len(plan.fired) == 1

    @pytest.mark.parametrize("engine", ENGINES)
    def test_no_faults_bit_equal_pin(self, setup, col, engine):
        """Acceptance pin: with faults disabled — no plan installed, or
        an installed-but-empty plan — the stack serves bit-identically
        to the plain pre-resilience dispatch (a direct collection
        search), across the engine matrix."""
        _, queries, _ = setup
        direct = col.search(
            queries[:8], k=10, r0=0.5, steps=6, engine=engine,
            interpret=True if engine != "jnp" else None,
        )
        d0, i0, reqs = _service(col, engine=engine).serve("res", queries[:8])
        with faults.active(FaultPlan()):  # installed, but scripts nothing
            d1, i1, _ = _service(col, engine=engine).serve("res", queries[:8])
        np.testing.assert_array_equal(d0, np.asarray(direct[0])[:, :10])
        np.testing.assert_array_equal(i0, np.asarray(direct[1])[:, :10])
        np.testing.assert_array_equal(d0, d1)
        np.testing.assert_array_equal(i0, i1)
        assert all(
            r.done and r.error is None and not r.degraded for r in reqs
        )


class TestBrownout:
    def _svc_with_bc(self, col, clk, **bc_kw):
        svc = _service(col, clock=clk, latency_window=4)
        bc = BrownoutController(svc, **bc_kw)
        assert svc.brownout is bc
        return svc, bc

    def test_ladder_escalates_and_heals(self, col):
        clk = FakeClock()
        svc, bc = self._svc_with_bc(col, clk, heal_after=2)
        breach = ["b"]  # any non-empty event list
        bc.observe(breach, clk.advance(1))
        assert bc.level == 1
        bc.observe(breach, clk.advance(1))
        bc.observe(breach, clk.advance(1))
        bc.observe(breach, clk.advance(1))
        assert bc.level == 3  # capped at max_level
        for _ in range(2):
            bc.observe([], clk.advance(1))
        assert bc.level == 2  # one rung per heal_after clean checks
        for _ in range(4):
            bc.observe([], clk.advance(1))
        assert bc.level == 0
        assert svc.registry.get("repro_store_brownout_level").value() == 0

    def test_hold_rate_limits_escalation(self, col):
        clk = FakeClock()
        _, bc = self._svc_with_bc(col, clk, hold_s=10.0)
        bc.observe(["b"], clk.advance(1))
        bc.observe(["b"], clk.advance(1))  # only 1s after the last rung
        assert bc.level == 1
        bc.observe(["b"], clk.advance(20))
        assert bc.level == 2

    def test_plans_degrade_per_rung(self, setup, col):
        _, queries, _ = setup
        clk = FakeClock()
        svc, bc = self._svc_with_bc(col, clk, step_cap_frac=0.5)
        r0 = svc.submit("res", queries[0])
        assert r0.plan.steps == 6 and not r0.degraded
        bc.observe(["b"], clk.advance(1))           # level 1: cap steps
        r1 = svc.submit("res", queries[1])
        assert r1.plan.steps == 3 and r1.degraded
        bc.observe(["b"], clk.advance(1))           # level 2: fixed floor
        r2 = svc.submit("res", queries[2])
        assert r2.plan.steps == 1 and r2.plan.termination is None
        assert r2.degraded
        svc.flush()
        assert all(r.done and r.error is None for r in (r0, r1, r2))
        assert svc.stats("res")["degraded"] == 2

    def test_shed_by_quota_weight(self, setup, col):
        _, queries, _ = setup
        clk = FakeClock()
        svc, bc = self._svc_with_bc(col, clk)
        svc.set_quota("gold", weight=5)
        svc.set_quota("bronze", weight=1)
        for _ in range(3):
            bc.observe(["b"], clk.advance(1))
        assert bc.level == 3
        with pytest.raises(BrownoutShed):
            svc.submit("res", queries[0], tenant="bronze")
        r = svc.submit("res", queries[0], tenant="gold")  # kept, degraded
        assert r.degraded
        assert svc.tenant_stats("bronze")["rejected"] == 1
        # equal weights shed nobody
        svc.set_quota("gold", weight=1)
        svc.submit("res", queries[1], tenant="bronze")
        svc.flush()

    def test_slo_watch_integration_escalates_then_heals(self, setup, col):
        """End to end: slow served traffic breaches the p99 ceiling via
        SLOWatch.check -> on_check -> escalate; once the (small) latency
        window refills with fast queries, clean checks heal the ladder
        back to healthy."""
        _, queries, _ = setup
        clk = FakeClock()
        svc, bc = self._svc_with_bc(col, clk, heal_after=2)
        slo = SLOWatch(
            svc.registry, "res", latency_p99_ms=10.0, min_samples=2,
            clock=clk,
        )
        bc.attach(slo)
        for q in queries[:4]:
            svc.submit("res", q)
        clk.advance(0.05)  # 50ms in queue -> p99 ~50ms
        svc.flush()
        assert slo.check(clk()) and bc.level == 1
        # traffic fast again: the 4-sample window forgets the spike
        for q in queries[:4]:
            svc.submit("res", q)
            svc.step(force=True)
        for _ in range(2):
            assert slo.check(clk.advance(1)) == []
        assert bc.level == 0


# ---------------------------------------------------------------------------
# Stragglers
# ---------------------------------------------------------------------------


class TestStragglers:
    def test_runtime_reexport_identity(self):
        from repro.runtime.fault_tolerance import (
            StragglerMonitor as RuntimeMonitor,
        )

        assert RuntimeMonitor is StragglerMonitor

    def test_monitor_flags_outlier_without_folding_it(self):
        mon = StragglerMonitor(alpha=0.5, threshold=2.0, warmup=3)
        assert not any(mon.record(i, 1.0) for i in range(4))
        assert mon.record(4, 10.0)
        assert mon.flagged == [(4, 10.0)]
        assert mon.ewma == 1.0  # the outlier never polluted the baseline

    def test_service_flags_slow_batch(self, setup, col):
        """Issue->complete wall time feeds the per-collection monitor: a
        batch 10x the EWMA baseline lands in straggler_batches."""
        _, queries, _ = setup
        clk = FakeClock()
        svc = _service(col, clock=clk, depth=1, max_wait_ms=0.0)
        for i in range(5):
            svc.submit("res", queries[i % len(queries)])
            svc.step()  # issues batch i; poll() completes batch i-1
            clk.advance(10.0 if i == 4 else 1.0)
        svc.flush()
        assert svc.stats("res")["straggler_batches"] == 1

    def test_shard_straggle_site_fires_in_sharded_search(self, setup):
        data, queries, kb = setup
        from repro.store import ShardedCollection

        mesh = make_mesh((1,), ("data",))
        scol = ShardedCollection.create(
            "straggle", kb, data[:64], mesh, c=1.5, w0=3.6, t=8, k=10
        )
        slept = []
        plan = FaultPlan(sleep=slept.append).add(
            "shard.straggle", arg=100.0, collection="straggle"
        )
        with faults.active(plan):
            scol.search(queries[:2], k=10, r0=0.5, steps=4)
        assert plan.fired and plan.fired[0][0] == "shard.straggle"
        assert slept == [pytest.approx(0.4)]  # 100ms * steps(4) scale
