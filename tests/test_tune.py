"""repro.tune test suite: adaptive termination, planning, policies.

Pins the subsystem's contracts (DESIGN.md §8):

* **FixedSchedule bit-equality** — the default policy resolves to
  exactly today's ``search_batch_fixed`` dispatch, bit for bit, through
  the planner and through the service;
* **C2-only / early-exit invisibility** — with C1 off, the adaptive
  while_loop path (and its batch-wide early exit) is bit-equal to the
  unrolled fixed schedule on every engine: the done masks freeze
  terminated queries' state, so adaptivity can only skip work;
* **C2 certification property** (hypothesis-style) — whenever the
  adaptive path terminates via C2 at radius r_i, the returned k-th best
  is ≤ c·r_i and the returned top-1 is within c²·r_i of the true NN
  (brute-force oracle), across the engine matrix × schedule lengths;
* **C1 candidate budget** — a tight budget terminates earlier than the
  fixed schedule, monotonically in the budget;
* **planner** — calibration-table monotonicity, RecallTarget minimality,
  LatencyBudget's measured-table requirement, uncalibrated fallbacks;
* **policy resolution** — request > collection > service, mirroring the
  engine-default resolution;
* **persistence** — search_policy + calibration survive
  snapshot/restore;
* **service integration** — recall_target routing, the per-query
  termination-step histogram in ``svc.stats()``, quantized cache keys
  (near-duplicate hits, version invalidation unchanged);
* **sharded parity** — per-shard termination on a 1-shard mesh equals
  the local adaptive path exactly.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st
from repro.compat import make_mesh
from repro.core import (
    DBLSHParams,
    Termination,
    build,
    search_batch_fixed,
)
from repro.core.distributed import build_sharded, search_sharded
from repro.data import make_clustered, normalize_scale
from repro.store import Collection, StoreService
from repro.store.cache import QueryResultCache
from repro.tune import (
    FixedSchedule,
    LatencyBudget,
    RecallTarget,
    ResolvedPlan,
    ScheduleTable,
    calibrate,
    certified_c2_mask,
    plan,
    resolve_policy,
    search_batch_adaptive,
    termination_step_histogram,
)

ENGINES = os.environ.get(
    "REPRO_STORE_TEST_ENGINES", "jnp kernel inline"
).replace(",", " ").split()

K_TEST = 8


@pytest.fixture(scope="module")
def setup():
    key = jax.random.key(31)
    kd, kb = jax.random.split(key)
    allpts = make_clustered(kd, 2096, 24, n_clusters=12, spread=0.02)
    data, queries = allpts[:2048], allpts[2048:]
    data, queries, _ = normalize_scale(data, queries)
    params = DBLSHParams.derive(
        n=2048, d=24, c=1.5, t=48, k=10, K=8, L=3,
        inline_vectors=True, max_blocks=16,
    )
    index = build(kb, data, params)
    return np.asarray(data), jnp.asarray(queries), index


def _bit_equal(a, b):
    da, ia = map(np.asarray, a[:2])
    db, ib = map(np.asarray, b[:2])
    np.testing.assert_array_equal(da, db)
    np.testing.assert_array_equal(ia, ib)


# ------------------------------------------------------------- adaptive core
@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("steps", [1, 4, 8])
def test_c2_only_adaptive_bit_equal_to_fixed(setup, engine, steps):
    """With C1 off, the while_loop adaptive path (early exit included)
    is bit-equal to the unrolled fixed schedule: C2's done mask is the
    same rule the fixed path already applies, and frozen state makes the
    early exit result-invisible."""
    data, queries, index = setup
    fixed = search_batch_fixed(
        index, queries, k=K_TEST, r0=0.3, steps=steps, engine=engine,
        interpret=True, exact=True, with_stats=True,
    )
    for early in (False, True):
        adaptive = search_batch_fixed(
            index, queries, k=K_TEST, r0=0.3, steps=steps, engine=engine,
            interpret=True, exact=True, with_stats=True,
            termination=Termination(use_c1=False, early_exit=early),
        )
        _bit_equal(fixed, adaptive)
        for key_ in ("radius_steps", "candidates"):
            np.testing.assert_array_equal(
                np.asarray(fixed[2][key_]), np.asarray(adaptive[2][key_])
            )


@pytest.mark.parametrize("engine", ENGINES)
def test_c2_certification_property(setup, engine):
    """Whenever the adaptive path terminates via C2 at radius r_i, the
    returned k-th best distance is ≤ c·r_i (the certificate) and the
    returned top-1 is within c²·r_i of the true NN (brute-force oracle),
    for every engine and schedule length."""
    data, queries, index = setup
    c = index.params.c
    # float64 diff-form oracle: core.brute_force uses the norm form,
    # whose cancellation floor at this coordinate scale exceeds the
    # bound slack the property checks
    X = np.asarray(data, np.float64)
    Qm = np.asarray(queries, np.float64)
    nn = np.sqrt(
        ((Qm[:, None, :] - X[None, :, :]) ** 2).sum(-1).min(axis=1)
    )

    checked = 0
    for steps in (4, 8, 12):
        for r0 in (0.1, 0.3):
            # exact=True: the property compares absolute distances to a
            # brute-force oracle, which sits below the norm-form fp
            # cancellation floor (DESIGN.md §7)
            d, i, stats = search_batch_adaptive(
                index, queries, k=K_TEST, r0=r0, steps=steps, engine=engine,
                interpret=True, exact=True,
                termination=Termination(use_c1=False),
            )
            d = np.asarray(d)
            mask = certified_c2_mask(
                d, stats, r0=r0, c=c, k=K_TEST, steps=steps
            )
            rs = np.asarray(stats["radius_steps"])
            r_i = r0 * np.power(c, np.maximum(rs, 1) - 1)
            tol = 1e-5
            for q in np.flatnonzero(mask):
                checked += 1
                assert d[q, K_TEST - 1] <= c * r_i[q] * (1 + tol)
                assert d[q, 0] - nn[q] <= c * c * r_i[q] * (1 + tol)
                # the certificate also bounds the answer against the
                # oracle directly: top-1 ≤ c·r_i and the true NN can
                # only be better
                assert d[q, 0] + tol >= nn[q] - tol
    assert checked > 0  # the property must actually have been exercised


@given(c1_budget=st.integers(16, 256))
@settings(deadline=None, max_examples=8)
def test_c1_budget_terminates_earlier(setup, c1_budget):
    """C1 is monotone: a candidate-count budget can only terminate
    queries no later than the fixed schedule, and per-query verified
    work / termination steps shrink monotonically as the budget
    tightens."""
    data, queries, index = setup
    fixed = search_batch_fixed(
        index, queries, k=K_TEST, r0=0.1, steps=10, with_stats=True,
    )
    adaptive = search_batch_fixed(
        index, queries, k=K_TEST, r0=0.1, steps=10, with_stats=True,
        termination=Termination(c1_budget=int(c1_budget)),
    )
    rs_f = np.asarray(fixed[2]["radius_steps"])
    rs_a = np.asarray(adaptive[2]["radius_steps"])
    assert (rs_a <= rs_f).all()
    assert (
        np.asarray(adaptive[2]["candidates"])
        <= np.asarray(fixed[2]["candidates"])
    ).all()


def test_termination_step_histogram(setup):
    data, queries, index = setup
    _, _, stats = search_batch_adaptive(
        index, queries, k=K_TEST, r0=0.1, steps=10,
    )
    hist = termination_step_histogram(stats, 10)
    assert hist.sum() == queries.shape[0]
    rs = np.asarray(stats["radius_steps"])
    assert hist[rs[0]] >= 1


# ------------------------------------------------------------------- planner
def test_calibration_table_shape_and_monotonicity(setup):
    data, queries, index = setup
    table = calibrate(index, queries[:16], k=K_TEST, steps_max=6)
    assert table.max_steps == 6
    assert table.c == index.params.c
    # windows nest: longer schedules only add candidates, so expected
    # recall and verified-slot cost are non-decreasing in steps
    assert all(
        b >= a - 1e-9 for a, b in zip(table.recall, table.recall[1:])
    )
    assert all(
        b >= a - 1e-9 for a, b in zip(table.cost_slots, table.cost_slots[1:])
    )


def test_recall_target_planning(setup):
    data, queries, index = setup
    table = calibrate(index, queries[:16], k=K_TEST, steps_max=8)
    achievable = max(table.recall)
    target = min(0.8, achievable)
    p = plan(table, RecallTarget(target))
    # minimal: meets the target, and one step fewer would miss it
    assert table.recall[p.steps - 1] >= target
    if p.steps > 1:
        assert table.recall[p.steps - 2] < target
    assert p.r0 == table.r0
    assert p.termination == Termination()
    # an unreachable target degrades to the best the table achieved,
    # capped by max_steps
    p_hi = plan(table, RecallTarget(2.0, max_steps=5))
    assert p_hi.steps == 5


def test_fixed_schedule_and_fallback_planning():
    p = plan(None, FixedSchedule(), default_r0=0.7, default_steps=6)
    assert p == ResolvedPlan(r0=0.7, steps=6, termination=None)
    p2 = plan(None, FixedSchedule(r0=0.2, steps=3))
    assert (p2.r0, p2.steps) == (0.2, 3)
    # RecallTarget without calibration: full default schedule + adaptive
    p3 = plan(None, RecallTarget(0.9), default_r0=0.7, default_steps=6)
    assert (p3.r0, p3.steps) == (0.7, 6)
    assert p3.termination is not None
    # ...still capped by the policy's max_steps latency guard
    assert plan(None, RecallTarget(0.9, max_steps=2),
                default_steps=8).steps == 2
    # LatencyBudget refuses to plan without measured milliseconds
    with pytest.raises(ValueError):
        plan(None, LatencyBudget(1.0))
    with pytest.raises(ValueError):
        plan(
            ScheduleTable(
                r0=0.5, c=1.5, k=8, recall=(1.0,), cost_slots=(10.0,),
                cost_ms=(float("nan"),), n_sample=4,
            ),
            LatencyBudget(1.0),
        )


def test_latency_budget_planning():
    table = ScheduleTable(
        r0=0.5, c=1.5, k=8,
        recall=(0.5, 0.8, 0.9, 0.95),
        cost_slots=(100.0, 200.0, 300.0, 400.0),
        cost_ms=(0.2, 0.5, 1.1, 2.4),
        n_sample=8,
    )
    assert plan(table, LatencyBudget(1.2)).steps == 3
    assert plan(table, LatencyBudget(0.1)).steps == 1   # floor: always search
    assert plan(table, LatencyBudget(10.0)).steps == 4
    assert plan(table, LatencyBudget(10.0, max_steps=2)).steps == 2


def test_policy_resolution_order():
    assert resolve_policy(None, None, None) is None
    svc_p = RecallTarget(0.5)
    col_p = FixedSchedule(steps=2)
    req_p = FixedSchedule(steps=3)
    assert resolve_policy(None, None, svc_p) is svc_p
    assert resolve_policy(None, col_p, svc_p) is col_p
    assert resolve_policy(req_p, col_p, svc_p) is req_p


# ------------------------------------------------- store / service integration
@pytest.fixture(scope="module")
def col(setup):
    data, queries, index = setup
    return Collection.from_index("tune", index, key=jax.random.key(5))


def test_fixed_schedule_policy_bit_equal_to_plain_dispatch(setup, col):
    """The satellite pin: FixedSchedule through the whole service stack
    (submit -> plan -> padded batch dispatch) returns bit-identical
    results to today's plain ``search_batch_fixed``."""
    data, queries, index = setup
    svc = StoreService(
        batch_shapes=(1, 4, 16), default_k=K_TEST, r0=0.3, steps=6,
        cache_size=0, inflight_depth=0,
    )
    svc.attach(col)
    Q = np.asarray(queries)[:16]
    d_plain, i_plain = search_batch_fixed(
        index, jnp.asarray(Q), k=K_TEST, r0=0.3, steps=6
    )
    d_pol, i_pol, reqs = svc.serve("tune", Q, policy=FixedSchedule())
    np.testing.assert_array_equal(np.asarray(d_plain), d_pol)
    np.testing.assert_array_equal(np.asarray(i_plain), i_pol)
    assert all(r.plan.termination is None for r in reqs)
    # ...and with no policy anywhere, the resolved plan is the same
    d_def, i_def, _ = svc.serve("tune", Q)
    np.testing.assert_array_equal(d_pol, d_def)
    np.testing.assert_array_equal(i_pol, i_def)


def test_service_recall_target_routes_through_planner(setup, col):
    data, queries, index = setup
    col.calibrate(queries[:16], k=K_TEST, steps_max=8)
    svc = StoreService(
        batch_shapes=(1, 4, 16), default_k=K_TEST, r0=0.3, steps=8,
        cache_size=0,
    )
    svc.attach(col)
    target = min(0.8, max(col.calibration.recall))
    expected = plan(col.calibration, RecallTarget(target))
    t = svc.submit("tune", np.asarray(queries[0]), recall_target=target)
    svc.flush()
    assert t.done
    assert t.plan == expected
    assert t.plan.r0 == col.calibration.r0
    assert 1 <= t.radius_steps <= t.plan.steps
    st_ = svc.stats("tune")
    hist = st_["termination_steps_hist"]
    assert sum(hist.values()) == st_["queries"]
    assert hist.get(t.radius_steps) >= 1
    with pytest.raises(ValueError):
        svc.submit("tune", np.asarray(queries[0]), recall_target=0.9,
                   policy=FixedSchedule())


def test_collection_policy_beats_service_default(setup):
    data, queries, index = setup
    c2 = Collection.from_index("c2", index, key=jax.random.key(6))
    c2.search_policy = FixedSchedule(steps=2)
    svc = StoreService(
        batch_shapes=(1, 4), default_k=K_TEST, r0=0.3, steps=8,
        cache_size=0, default_policy=FixedSchedule(steps=5),
    )
    svc.attach(c2)
    # collection policy wins over the service default...
    assert svc.resolve_plan("c2").steps == 2
    # ...and an explicit request policy wins over both
    assert svc.resolve_plan("c2", FixedSchedule(steps=3)).steps == 3
    t = svc.submit("c2", np.asarray(queries[0]))
    svc.flush()
    assert t.plan.steps == 2 and t.radius_steps <= 2


def test_search_policy_and_calibration_snapshot_roundtrip(setup, tmp_path):
    data, queries, index = setup
    c3 = Collection.from_index("c3", index, key=jax.random.key(7))
    c3.search_policy = RecallTarget(0.8, max_steps=9)
    table = c3.calibrate(queries[:12], k=K_TEST, steps_max=5)
    c3.snapshot(str(tmp_path))
    r = Collection.restore(str(tmp_path))
    assert r.search_policy == c3.search_policy
    assert r.calibration.r0 == table.r0
    assert r.calibration.recall == table.recall
    assert r.calibration.cost_slots == table.cost_slots
    # NaN-aware: unmeasured cost_ms round-trips as NaN
    np.testing.assert_array_equal(
        np.isnan(r.calibration.cost_ms), np.isnan(table.cost_ms)
    )
    # the restored table plans identically
    assert plan(r.calibration, r.search_policy) == plan(
        table, c3.search_policy
    )


def test_quantized_cache_keys(setup):
    """Satellite pin: opt-in eps-bucketing widens hits to near-duplicate
    queries; version invalidation semantics are untouched."""
    data, queries, index = setup
    cache = QueryResultCache(capacity=16, quantize_eps=1e-3)
    # align the probe query to eps-cell anchors so the ±1e-5 perturbation
    # below deterministically stays inside the cell
    q = (np.round(np.asarray(queries[0]) / 1e-3) * 1e-3).astype(np.float32)
    k1 = cache.key("a", 1, q, 8, "jnp", 0.5, 6)
    k2 = cache.key("a", 1, q + 1e-5, 8, "jnp", 0.5, 6)
    assert k1 == k2                       # same eps cell -> same key
    far = cache.key("a", 1, q + 1.0, 8, "jnp", 0.5, 6)
    assert far != k1
    assert cache.key("a", 2, q, 8, "jnp", 0.5, 6) != k1  # version differs
    # default (exact) keys still require bit-equality
    exact = QueryResultCache(capacity=16)
    assert exact.key("a", 1, q, 8, "jnp", 0.5, 6) != exact.key(
        "a", 1, q + 1e-5, 8, "jnp", 0.5, 6
    )
    # termination joins the key: a planned adaptive result must never be
    # served for a fixed-schedule request
    assert cache.key("a", 1, q, 8, "jnp", 0.5, 6, Termination()) != k1

    # service level: near-duplicate hit, then invalidation on mutation
    col = Collection.create(
        "qc", jax.random.key(9), data[:512], c=1.5, t=24, k=8, K=6, L=2,
    )
    svc = StoreService(
        batch_shapes=(1, 4), default_k=K_TEST, r0=0.3, steps=4,
        cache_quantize_eps=1e-3,
    )
    svc.attach(col)
    t0 = svc.submit("qc", q)
    svc.flush()
    t1 = svc.submit("qc", q + 1e-5)
    svc.flush()
    assert t1.cached
    np.testing.assert_array_equal(t0.ids, t1.ids)
    col.add(np.asarray(queries[1])[None, :])
    t2 = svc.submit("qc", q)
    svc.flush()
    assert not t2.cached


def test_sharded_termination_parity(setup):
    """Per-shard termination on a 1-shard mesh equals the local adaptive
    path exactly (the n-shard argument is monotonicity: a shard's local
    k-th ≥ the global k-th, so local C2 only fires later)."""
    data, queries, index = setup
    mesh = make_mesh((1,), ("data",))
    params = index.params
    # identical hash functions on both sides: build local + sharded from
    # the same key (the fixture's index used a different split)
    kb = jax.random.key(77)
    local = build(kb, jnp.asarray(data), params)
    sharded = build_sharded(kb, jnp.asarray(data), params, mesh)
    term = Termination(c1_budget=64)
    ds, is_, ss = search_sharded(
        sharded, queries, k=K_TEST, r0=0.2, steps=6, mesh=mesh,
        with_stats=True, termination=term,
    )
    dl, il, sl = search_batch_fixed(
        local, queries, k=K_TEST, r0=0.2, steps=6, with_stats=True,
        termination=term,
    )
    np.testing.assert_array_equal(np.asarray(is_), np.asarray(il))
    np.testing.assert_array_equal(
        np.asarray(ss["radius_steps"]), np.asarray(sl["radius_steps"])
    )
    np.testing.assert_array_equal(
        np.asarray(ss["candidates"]), np.asarray(sl["candidates"])
    )
