"""Sharded collection lifecycle: the unified mutable protocol.

Tier-1 coverage runs on a 1-shard mesh (CPU hosts expose one device);
the protocol — insert routing, strided stable ids, global-id delete
translation, rebalancing compaction with a gathered id remap, payload
ride-along, snapshot / restore (including the elastic migration path),
version-clock cache invalidation — is identical at any shard count, and
the P=8 routing/balance/migration cases live in
``tests/test_distributed.py::test_sharded_lifecycle_8dev``.

The engine matrix (``REPRO_STORE_TEST_ENGINES``) drives the service
tests: the sharded placement pins per-shard verification to jnp via
``fixed_engine``, so every requested engine must resolve to honest
jnp-labelled tickets.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hypothesis import given, settings
from hypothesis import strategies as st
from repro.compat import make_mesh
from repro.core import DBLSHParams, brute_force
from repro.core.distributed import build_sharded, search_sharded
from repro.store import (
    Collection,
    CompactionPolicy,
    ShardedCollection,
    StoreService,
    open_collection,
    restore_collection,
)
from repro.tune import RecallTarget

ENGINES = os.environ.get(
    "REPRO_STORE_TEST_ENGINES", "jnp"
).replace(",", " ").split()


@pytest.fixture(scope="module")
def setup():
    from repro.data import make_clustered, normalize_scale

    kd, kb = jax.random.split(jax.random.key(29))
    allpts = make_clustered(kd, 1032, 16, n_clusters=8, spread=0.02)
    pts, q, _ = normalize_scale(allpts[:1000], allpts[1000:])
    allpts = np.concatenate([np.asarray(pts), np.asarray(q)])
    data, extra, queries = allpts[:800], allpts[800:1000], allpts[1000:]
    return data, extra, queries, kb


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1,), ("data",))


def _make(name, kb, data, mesh, **kw):
    kw.setdefault("policy", CompactionPolicy(auto=False))
    return ShardedCollection.create(
        name, kb, data, mesh, c=1.5, w0=3.6, t=32, k=10, **kw
    )


def _recall(ids, gt_i, k=10):
    return np.mean(
        [len(set(a.tolist()) & set(b.tolist())) / k
         for a, b in zip(np.asarray(ids), np.asarray(gt_i))]
    )


# ---------------------------------------------------------------------------
# Mutations: add / remove / compact against brute force (acceptance
# criterion: results match a fresh index on the post-mutation point set)
# ---------------------------------------------------------------------------


def test_sharded_add_routes_and_keeps_payload(setup, mesh):
    data, extra, queries, kb = setup
    col = _make("sa", kb, data, mesh, payload=np.arange(800))
    assert col.live_count() == 800
    v0 = col.version

    ids = col.add(extra[:50], payload=np.arange(800, 850))
    assert col.live_count() == 850 and col.n == 850
    assert col.version > v0  # mutation bumped the shared clock
    assert col.stats.inserted == 50

    # exact-match query on an inserted point returns its current id + tag
    q = extra[7:8]
    d, i = col.search(q, k=1, r0=0.25, steps=8, exact=True)
    assert float(d[0, 0]) < 1e-3
    assert int(i[0, 0]) == int(ids[7])
    assert int(np.asarray(col.get_payload(i))[0, 0]) == 800 + 7


def test_sharded_remove_never_returned(setup, mesh):
    data, extra, queries, kb = setup
    col = _make("sr", kb, data, mesh)
    _, gt = brute_force(jnp.asarray(data), jnp.asarray(queries), k=5)
    victims = np.unique(np.asarray(gt).reshape(-1))[:40].astype(np.int32)
    col.remove(victims)
    assert col.live_count() == 800 - len(victims)
    assert col.stats.deleted == len(victims)
    d, ids = col.search(queries, k=10, r0=0.5, steps=8)
    fin = np.isfinite(np.asarray(d))
    leaked = set(victims.tolist()) & set(
        np.asarray(ids)[fin].reshape(-1).tolist()
    )
    assert not leaked, leaked


@given(seed=st.integers(0, 10_000))
@settings(deadline=None, max_examples=3)
def test_sharded_update_roundtrip_vs_brute_force(setup, mesh, seed):
    """Property: add -> remove -> compact on a ShardedCollection
    round-trips against a brute-force scan of the surviving point set,
    and (on one shard, where compaction needs no padding) the compacted
    index is *bit-identical* to a fresh sharded build of the survivors
    with the same key — the strongest form of fresh-build parity."""
    data, extra, queries, kb = setup
    rng = np.random.default_rng(seed)
    m = int(rng.integers(16, 96))
    col = _make("sp", kb, data, mesh, payload=np.arange(800))

    ids = col.add(extra[:m], payload=np.arange(800, 800 + m))
    n_tot = 800 + m
    assert col.live_count() == n_tot
    assert ids.dtype == np.int32  # int32 end to end

    n_del = int(rng.integers(10, 120))
    del_ids = rng.choice(n_tot, size=n_del, replace=False).astype(np.int32)
    del_tags = np.asarray(col.get_payload(del_ids[None]))[0].astype(int)
    col.remove(del_ids)
    assert col.live_count() == n_tot - n_del

    # deleted ids can never be returned, even pre-compaction
    d, got = col.search(queries, k=10, r0=0.5, steps=8)
    fin = np.isfinite(np.asarray(d))
    leaked = set(del_ids.tolist()) & set(
        np.asarray(got)[fin].reshape(-1).tolist()
    )
    assert not leaked, leaked

    key_pred = jax.random.split(col._key)[1]  # the key compact will use
    id_map = col.compact()
    n_live = n_tot - n_del
    assert col.n == n_live and col.live_count() == n_live
    assert int((id_map >= 0).sum()) == n_live
    assert np.all(id_map[del_ids] == -1)
    assert np.array_equal(
        np.sort(id_map[id_map >= 0]), np.arange(n_live)
    )

    # payload followed the remap: survivors keep their tags in old-id
    # order (the strided buffer's tail is headroom — zeros, unallocated)
    full = np.concatenate([data, extra[:m]])
    live_mask = np.ones(n_tot, bool)
    live_mask[del_tags] = False  # P=1: tag == original id == global id
    np.testing.assert_array_equal(
        np.asarray(col.payload)[:n_live], np.flatnonzero(live_mask)
    )
    assert np.all(np.asarray(col.payload)[n_live:] == 0)

    # bit-exact fresh-build parity on one shard: same survivors, same
    # key, same id stride (the stride sets the merge sentinel)
    survivors = full[live_mask]
    params = DBLSHParams.derive(
        n=n_live, d=16, c=1.5, w0=3.6, t=32, k=10
    )
    fresh = build_sharded(key_pred, jnp.asarray(survivors), params, mesh,
                          stride=col.sharded.stride)
    d_c, i_c = col.search(queries, k=10, r0=0.5, steps=8)
    d_f, i_f = search_sharded(
        fresh, jnp.asarray(queries), k=10, r0=0.5, steps=8, mesh=mesh
    )
    np.testing.assert_array_equal(np.asarray(i_c), np.asarray(i_f))
    np.testing.assert_array_equal(np.asarray(d_c), np.asarray(d_f))


@given(seed=st.integers(0, 10_000))
@settings(deadline=None, max_examples=3)
def test_sharded_ids_stable_across_adds(setup, mesh, seed):
    """Property (the PR's id contract): ids returned by ``add`` stay
    valid — exact-searchable and removable — across at least three
    subsequent adds, with no remap and no compaction.  The stride
    headroom absorbs the growth, so held ids are durable handles."""
    data, extra, queries, kb = setup
    rng = np.random.default_rng(seed)
    col = _make("stable", kb, data, mesh, payload=np.arange(800))
    assert col.sharded.stride >= 2 * col.sharded.n_local

    held = col.add(extra[:20], payload=np.arange(800, 820))
    held = np.asarray(held).copy()
    off = 20
    for _ in range(3):  # >= 3 subsequent adds
        m = int(rng.integers(8, 40))
        col.add(extra[off:off + m], payload=np.arange(800 + off, 800 + off + m))
        off += m
    assert col.stats.compactions == 0  # no renumbering happened

    # every held id still resolves: exact search returns it verbatim
    probe = rng.choice(20, size=5, replace=False)
    d, i = col.search(extra[probe], k=1, r0=0.25, steps=8, exact=True)
    assert np.all(np.asarray(d)[:, 0] < 1e-3)
    np.testing.assert_array_equal(np.asarray(i)[:, 0], held[probe])
    np.testing.assert_array_equal(
        np.asarray(col.get_payload(held[None]))[0], 800 + np.arange(20)
    )

    # and still removes: the tombstoned handles never return
    col.remove(held)
    d2, i2 = col.search(extra[:20], k=5, r0=0.5, steps=8)
    fin = np.isfinite(np.asarray(d2))
    leaked = set(held.tolist()) & set(
        np.asarray(i2)[fin].reshape(-1).tolist()
    )
    assert not leaked, leaked


def test_sharded_stride_exhaustion_forces_renumber(setup, mesh):
    """An add that would overflow the id stride triggers exactly one
    compact (the sanctioned renumbering event) and then lands in the
    fresh headroom — even with auto-compaction off."""
    data, extra, queries, kb = setup
    col = _make("ovf", kb, data[:40], mesh, payload=np.arange(40))
    stride0 = col.sharded.stride
    assert stride0 == 80  # headroom 2.0 over 40
    ids = col.add(extra[:50], payload=np.arange(40, 90))  # 90 > 80
    assert col.stats.compactions == 1
    assert col.sharded.stride >= 90 and col.live_count() == 90
    # the batch's ids are valid post-renumber handles
    d, i = col.search(extra[3:4], k=1, r0=0.25, steps=8, exact=True)
    assert float(d[0, 0]) < 1e-3 and int(i[0, 0]) == int(ids[3])
    assert int(np.asarray(col.get_payload(i))[0, 0]) == 43


def test_sharded_restore_migrated_rebalances(setup, mesh, tmp_path):
    """The elastic restore path (forced here with ``migrate=True``; a
    genuine P' != P runs in the 8-device script): manifest rows are
    re-partitioned and rebuilt, ids renumber, payload follows its
    points, calibration is dropped as stale."""
    data, extra, queries, kb = setup
    col = _make("el", kb, data, mesh, payload=np.arange(800))
    col.add(extra[:30], payload=np.arange(800, 830))
    col.remove(np.arange(0, 60, 2).astype(np.int32))  # 30 victims
    col.calibrate(queries[:12], k=10)
    step = col.snapshot(str(tmp_path))

    col2 = ShardedCollection.restore(str(tmp_path), mesh=mesh, step=step,
                                     migrate=True)
    assert col2.live_count() == col.live_count() == 800
    assert col2.n == 800  # migration also compacts the tombstones away
    assert col2.calibration is None  # geometry changed: table is stale
    assert col2.version > col.version

    # recall parity vs brute force over the survivors, matched by tag
    # (ids renumbered, the payload is the stable identity)
    full = np.concatenate([data, extra[:30]])
    alive = np.ones(830, bool)
    alive[np.arange(0, 60, 2)] = False
    alive_tags = np.flatnonzero(alive)
    gd, gt = brute_force(jnp.asarray(full[alive_tags]),
                         jnp.asarray(queries), k=10)
    d2, i2 = col2.search(queries, k=10, r0=0.5, steps=8)
    tags2 = np.asarray(col2.get_payload(i2)).astype(int)
    recs = []
    for qi in range(queries.shape[0]):
        f = np.isfinite(np.asarray(d2)[qi])
        want = alive_tags[np.asarray(gt)[qi]]
        recs.append(len(set(tags2[qi][f].tolist()) & set(want.tolist())) / 10)
    assert float(np.mean(recs)) > 0.6, recs

    # migrate=False demands the bit-identical path — and still works on
    # the equal mesh
    col3 = ShardedCollection.restore(str(tmp_path), mesh=mesh, step=step,
                                     migrate=False)
    d3, i3 = col3.search(queries, k=10, r0=0.5, steps=8)
    da, ia = col.search(queries, k=10, r0=0.5, steps=8)
    np.testing.assert_array_equal(np.asarray(i3), np.asarray(ia))
    np.testing.assert_array_equal(np.asarray(d3), np.asarray(da))


def test_get_payload_clamps_both_ends(setup, mesh):
    """A negative id (e.g. -1 from an id map marking a deletion) clamps
    to row 0 instead of wrapping to the buffer tail."""
    data, extra, queries, kb = setup
    col = _make("clamp", kb, data[:100], mesh, payload=np.arange(100) + 7)
    out = np.asarray(col.get_payload(np.array([[-1, -100, 0]])))[0]
    np.testing.assert_array_equal(out, [7, 7, 7])
    # sentinel (id_space) clamps to the last buffer row, as documented
    sent = np.asarray(col.get_payload(np.array([col.id_space])))
    assert sent.shape == (1,)


def test_sharded_auto_compaction_policy_fires(setup, mesh):
    """Growth past the policy ratio triggers compaction through the
    shared lifecycle template, exactly like a local collection."""
    data, extra, queries, kb = setup
    col = _make(
        "sg", kb, data[:100], mesh,
        policy=CompactionPolicy(growth_ratio=1.5, auto=True),
    )
    built0 = col.built_n
    # 150 >= 1.5 * 100 -> compact; the batch also exactly fills the id
    # stride (sized to the growth ratio), so the policy — not a forced
    # stride renumber — is what fires
    col.add(data[100:150])
    assert col.stats.compactions == 1
    assert col.built_n == 150 > built0
    assert col.live_count() == 150
    # hollowness trigger: tombstone most points
    col2 = _make(
        "sh2", kb, data[:200], mesh,
        policy=CompactionPolicy(min_live_ratio=0.5, auto=True),
    )
    col2.remove(np.arange(0, 101))
    assert col2.stats.compactions == 1
    assert col2.live_count() == 99


# ---------------------------------------------------------------------------
# Snapshot / restore (acceptance criterion: fresh version, payload +
# policy + schedule table preserved)
# ---------------------------------------------------------------------------


def test_sharded_snapshot_restore_roundtrip(setup, mesh, tmp_path):
    data, extra, queries, kb = setup
    col = _make(
        "ck", kb, data, mesh, payload=np.arange(800),
        policy=CompactionPolicy(growth_ratio=3.0, auto=False),
        search_policy=RecallTarget(0.9),
    )
    col.add(extra[:30], payload=np.arange(800, 830))
    col.remove(np.arange(5))
    table = col.calibrate(queries[:16], k=10)
    d0, i0 = col.search(queries, k=10, r0=0.5, steps=8)
    step = col.snapshot(str(tmp_path))

    col2 = restore_collection(str(tmp_path), step, mesh=mesh)
    assert isinstance(col2, ShardedCollection)
    assert col2.name == "ck"
    assert col2.version > col.version  # fresh, never aliased
    assert col2.policy == col.policy
    assert col2.search_policy == RecallTarget(0.9)
    assert col2.calibration is not None
    assert col2.calibration.recall == table.recall
    assert col2.calibration.cost_slots == table.cost_slots
    assert (col2.calibration.r0, col2.calibration.k) == (table.r0, table.k)
    assert col2.built_n == col.built_n
    assert col2.live_count() == col.live_count()
    d1, i1 = col2.search(queries, k=10, r0=0.5, steps=8)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d0))
    np.testing.assert_array_equal(
        np.asarray(col2.payload), np.asarray(col.payload)
    )

    # restored collections keep evolving deterministically: the preserved
    # key makes the next compaction identical across the boundary
    col.compact()
    col2.compact()
    _, i2a = col.search(queries, k=10, r0=0.5, steps=8)
    _, i2b = col2.search(queries, k=10, r0=0.5, steps=8)
    np.testing.assert_array_equal(np.asarray(i2a), np.asarray(i2b))


def test_snapshot_placement_dispatch(setup, mesh, tmp_path):
    """Cross-placement restores fail loudly; restore_collection routes
    from the manifest alone."""
    data, extra, queries, kb = setup
    col = _make("pd", kb, data[:200], mesh)
    step = col.snapshot(str(tmp_path / "sharded"))
    with pytest.raises(ValueError, match="sharded"):
        Collection.restore(str(tmp_path / "sharded"), step)
    with pytest.raises(ValueError, match="mesh"):
        restore_collection(str(tmp_path / "sharded"), step)

    local = Collection.create("pl", kb, data[:200], c=1.5, w0=3.6, t=8, k=5)
    lstep = local.snapshot(str(tmp_path / "local"))
    with pytest.raises(ValueError, match="local"):
        ShardedCollection.restore(
            str(tmp_path / "local"), mesh=mesh, step=lstep
        )
    back = restore_collection(str(tmp_path / "local"), lstep)
    assert isinstance(back, Collection)


# ---------------------------------------------------------------------------
# Auto re-calibration hook (ROADMAP tune item — both placements)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("placement", ["local", "sharded"])
def test_compact_invalidates_and_refits_calibration(setup, mesh, placement):
    data, extra, queries, kb = setup
    if placement == "local":
        col = Collection.create(
            "cal_l", kb, data, c=1.5, w0=3.6, t=32, k=10,
            policy=CompactionPolicy(auto=False),
        )
    else:
        col = _make("cal_s", kb, data, mesh)

    # without retained queries: compact just invalidates
    col.calibrate(queries[:12], k=10)
    assert col.calibration is not None
    col.remove(np.arange(3))
    col.compact()
    assert col.calibration is None

    # with retain=True: compact re-fits automatically from the retained
    # sample (r0 re-derives against the rebuilt geometry)
    t0 = col.calibrate(queries[:12], k=10, retain=True)
    col.remove(np.arange(3))
    col.compact()
    assert col.calibration is not None and col.calibration is not t0
    assert col.calibration.max_steps == t0.max_steps
    # the refitted table plans: a recall target resolves to a schedule
    plan = col.plan(RecallTarget(0.5))
    assert 1 <= plan.steps <= col.calibration.max_steps


# ---------------------------------------------------------------------------
# Service integration: one lifecycle/cache/policy path for both placements
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ENGINES)
def test_sharded_mutations_invalidate_service_cache(setup, mesh, engine):
    """The stale-cache script, sharded: add / remove / compact / restore
    each bump the shared version clock, so repeat queries recompute and
    match a fresh sharded search — never yesterday's index.  The service
    engine default comes from the matrix; fixed_engine pins the honest
    jnp label either way."""
    data, extra, queries, kb = setup
    col = _make("inv", kb, data, mesh, payload=np.arange(800))
    svc = StoreService(
        batch_shapes=(8,), max_wait_ms=1e9, default_k=10, r0=0.5, steps=8,
        engine=engine, interpret=True if engine != "jnp" else None,
        cache_size=256,
    )
    svc.attach(col)
    Q = queries[:8]

    def check_round(expect_cached):
        reqs = [svc.submit("inv", q) for q in Q]
        svc.flush()
        assert all(r.done for r in reqs)
        assert all(r.engine == "jnp" for r in reqs)  # fixed_engine pins
        assert all(r.cached == expect_cached for r in reqs)
        want_d, want_i = col.search(Q, k=10, r0=0.5, steps=8)
        np.testing.assert_array_equal(
            np.stack([r.ids for r in reqs]), np.asarray(want_i)
        )
        np.testing.assert_array_equal(
            np.stack([r.dists for r in reqs]), np.asarray(want_d)
        )
        return reqs

    check_round(False)
    check_round(True)  # warm: identical repeat hits
    col.add(extra[:16], payload=np.arange(800, 816))
    check_round(False)  # add invalidated
    check_round(True)
    col.remove(np.arange(4))
    check_round(False)  # remove invalidated
    col.compact()
    check_round(False)  # compact invalidated
    reqs = check_round(True)
    assert all(r.payload is not None and r.payload.shape == (10,)
               for r in reqs)


def test_sharded_restore_does_not_alias_cache(setup, mesh, tmp_path):
    """Divergent histories from one sharded snapshot must not share
    cache entries (same contract as local restore)."""
    data, extra, queries, kb = setup
    col = _make("al", kb, data[:300], mesh)
    svc = StoreService(
        batch_shapes=(4,), max_wait_ms=1e9, default_k=5, r0=0.5, steps=4,
        cache_size=64,
    )
    svc.attach(col)
    step = col.snapshot(str(tmp_path))
    Q = queries[:4]
    _ = [svc.submit("al", q) for q in Q]
    svc.flush()
    hits0 = svc.cache.hits
    col.add(extra[:16])  # diverge the live collection
    restored = restore_collection(str(tmp_path), step, mesh=mesh)
    svc.collections["al"] = restored
    reqs = [svc.submit("al", q) for q in Q]
    svc.flush()
    assert svc.cache.hits == hits0  # no hit against either old version
    want_d, want_i = restored.search(Q, k=5, r0=0.5, steps=4)
    np.testing.assert_array_equal(
        np.stack([r.ids for r in reqs]), np.asarray(want_i)[:, :5]
    )


# ---------------------------------------------------------------------------
# Router / engine validation (the silent-drop fixes)
# ---------------------------------------------------------------------------


def test_open_collection_forwards_lifecycle_options(setup):
    """``open_collection`` no longer drops policy/search_policy on any
    path (the sharded branch is exercised in the 8-device script — a
    1-device mesh can never fan out)."""
    data, extra, queries, kb = setup
    col = open_collection(
        "opt", kb, data[:200], mesh=None, c=1.5, w0=3.6, t=8, k=5,
        policy=CompactionPolicy(growth_ratio=9.9),
        search_policy=RecallTarget(0.7),
    )
    assert isinstance(col, Collection)
    assert col.policy.growth_ratio == 9.9
    assert col.search_policy == RecallTarget(0.7)


def test_sharded_rejects_unhonorable_engine(setup, mesh):
    data, extra, queries, kb = setup
    with pytest.raises(ValueError, match="jnp engine"):
        _make("bad", kb, data[:200], mesh, engine="kernel")
    col = _make("ok", kb, data[:200], mesh, engine="jnp")
    assert col.default_engine == "jnp" and col.fixed_engine == "jnp"
