"""Vector-store subsystem tests: micro-batching service equivalence,
auto-compaction policy, payload alignment, persistence round-trip, and
the sharded router surface."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.compat import make_mesh
from repro.core import brute_force, search_batch_fixed
from repro.data import make_clustered, normalize_scale
from repro.store import (
    Collection,
    CompactionPolicy,
    ShardedCollection,
    StoreService,
    open_collection,
)


@pytest.fixture(scope="module")
def setup():
    kd, kb = jax.random.split(jax.random.key(17))
    allpts = make_clustered(kd, 1232, 16, n_clusters=10, spread=0.02)
    data, queries = allpts[:1200], allpts[1200:]
    data, queries, _ = normalize_scale(data, queries)
    return np.asarray(data), np.asarray(queries), kb


def _recall(ids, gt_i, k):
    return np.mean(
        [len(set(a.tolist()) & set(b.tolist())) / k
         for a, b in zip(np.asarray(ids), np.asarray(gt_i))]
    )


# ---------------------------------------------------------------------------
# StoreService: micro-batching equivalence (acceptance criterion)
# ---------------------------------------------------------------------------


def test_service_stream_matches_direct_batch(setup):
    """A mixed stream of single queries through the admission queue must
    return results identical to one direct search_batch_fixed call —
    padding to fixed batch shapes introduces no drift."""
    data, queries, kb = setup
    k = 10
    col = Collection.create("s", kb, data, c=1.5, w0=3.6, t=32, k=k)
    svc = StoreService(batch_shapes=(1, 4, 16), default_k=k, r0=0.5, steps=8)
    svc.attach(col)

    # mixed stream: irregular arrival chunks -> batches of size 3, 7, 1,
    # 16, 5 (each padded to the smallest fitting shape)
    reqs = []
    cuts = [3, 10, 11, 27, 32]
    start = 0
    for cut in cuts:
        for q in queries[start:cut]:
            reqs.append(svc.submit("s", q))
        svc.step(force=True)
        start = cut
    assert svc.pending() == 0
    assert all(r.done for r in reqs)

    d_direct, i_direct = search_batch_fixed(
        col.index, jnp.asarray(queries), k=k, r0=0.5, steps=8
    )
    np.testing.assert_array_equal(
        np.stack([r.ids for r in reqs]), np.asarray(i_direct)
    )
    np.testing.assert_array_equal(
        np.stack([r.dists for r in reqs]), np.asarray(d_direct)
    )

    stats = svc.stats("s")
    assert stats["queries"] == queries.shape[0]
    assert stats["batches"] == len(cuts)
    assert 0 < stats["mean_radius_steps"] <= 8
    assert stats["mean_candidates"] > 0
    assert 0 < stats["padding_efficiency"] <= 1.0


def test_service_per_request_k_sliced(setup):
    """Requests with k below the service default get a sliced prefix of
    the service-k result (no recompilation per k)."""
    data, queries, kb = setup
    col = Collection.create("s2", kb, data, c=1.5, w0=3.6, t=32, k=10)
    svc = StoreService(batch_shapes=(4,), default_k=10, r0=0.5, steps=8)
    svc.attach(col)
    r_small = svc.submit("s2", queries[0], k=3)
    r_full = svc.submit("s2", queries[0], k=10)
    svc.flush()
    assert r_small.ids.shape == (3,)
    np.testing.assert_array_equal(r_small.ids, r_full.ids[:3])
    with pytest.raises(ValueError):
        svc.submit("s2", queries[0], k=11)


# ---------------------------------------------------------------------------
# Auto-compaction policy (acceptance criterion)
# ---------------------------------------------------------------------------


def test_auto_compaction_restores_recall(setup):
    """A stream of small adds growing the collection past 2x the built n
    must trigger compact, and recall@10 vs brute force on the grown
    dataset must be >= the never-compacted recall."""
    data, queries, kb = setup
    base, extra = data[:500], data[500:1200]
    k = 10

    def make(auto):
        return Collection.create(
            "g", jax.random.key(17), base, c=1.5, w0=3.6, t=32, k=k,
            policy=CompactionPolicy(growth_ratio=2.0, auto=auto),
        )

    frozen, managed = make(False), make(True)
    for j in range(0, 700, 35):  # 20 small appends -> sparse padded blocks
        frozen.add(extra[j:j + 35])
        managed.add(extra[j:j + 35])

    assert frozen.stats.compactions == 0
    assert managed.stats.compactions >= 1
    assert managed.n == frozen.n == 1200
    assert managed.built_n >= 1000  # policy fired at the 2x threshold
    # the rebuild re-derives K for the grown n (K ~ log n)
    assert managed.index.params.K >= frozen.index.params.K
    # and packs away the per-add padding waste
    assert managed.index.nb < frozen.index.nb

    _, gt_i = brute_force(jnp.asarray(data), jnp.asarray(queries), k=k)
    _, ids_pre = frozen.search(queries, k=k, r0=0.5, steps=8)
    _, ids_post = managed.search(queries, k=k, r0=0.5, steps=8)
    rec_pre, rec_post = _recall(ids_pre, gt_i, k), _recall(ids_post, gt_i, k)
    assert rec_post >= rec_pre, (rec_pre, rec_post)
    assert rec_post > 0.85, rec_post


def test_hollowness_triggers_compaction(setup):
    """Deleting past min_live_ratio triggers a rebuild that reclaims
    tombstoned slots and remaps payload ids."""
    data, _, kb = setup
    col = Collection.create(
        "h", kb, data[:600], c=1.5, w0=3.6, t=32, k=10,
        payload=np.arange(600),
        policy=CompactionPolicy(min_live_ratio=0.5),
    )
    col.remove(np.arange(0, 301))  # live 299/600 < 0.5
    assert col.stats.compactions == 1
    assert col.n == 299
    assert col.live_count() == 299
    # payload rows followed the compaction id map
    np.testing.assert_array_equal(np.asarray(col.payload), np.arange(301, 600))


def test_payload_alignment_through_updates(setup):
    """add -> remove -> compact keeps payload aligned: querying exactly on
    a surviving point returns its original payload tag."""
    data, _, kb = setup
    base, extra = data[:500], data[500:600]
    col = Collection.create(
        "p", kb, base, c=1.5, w0=3.6, t=32, k=10,
        payload=np.arange(500), policy=CompactionPolicy(auto=False),
    )
    new_ids = col.add(extra, payload=np.arange(500, 600))
    np.testing.assert_array_equal(new_ids, np.arange(500, 600))
    col.remove(np.arange(0, 50))
    col.compact()
    assert col.stats.compactions == 1 and col.n == 550

    probe_tag = 570  # an inserted, surviving point
    d, ids = col.search(data[probe_tag:probe_tag + 1], k=1, r0=0.25, steps=8)
    assert float(d[0, 0]) < 1e-3
    tag = int(np.asarray(col.get_payload(ids))[0, 0])
    assert tag == probe_tag


# ---------------------------------------------------------------------------
# Persistence: snapshot / restore round-trip (acceptance criterion)
# ---------------------------------------------------------------------------


def test_snapshot_restore_identical_results(setup, tmp_path):
    """save -> restore -> bit-identical search results, with payload,
    policy, counters, and the compaction PRNG key preserved."""
    data, queries, kb = setup
    col = Collection.create(
        "ck", kb, data, c=1.5, w0=3.6, t=32, k=10, payload=np.arange(1200),
        policy=CompactionPolicy(growth_ratio=3.0),
    )
    d0, i0 = col.search(queries, k=10, r0=0.5, steps=8)
    step = col.snapshot(str(tmp_path))

    col2 = Collection.restore(str(tmp_path), step)
    assert col2.name == "ck"
    assert col2.index.params == col.index.params
    assert col2.policy == col.policy
    assert col2.built_n == col.built_n
    d1, i1 = col2.search(queries, k=10, r0=0.5, steps=8)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d0))
    np.testing.assert_array_equal(np.asarray(col2.payload), np.asarray(col.payload))

    # restored collections keep evolving: the preserved key makes the next
    # compaction deterministic across the save/restore boundary
    col.remove(np.arange(100))
    col2.remove(np.arange(100))
    col.compact()
    col2.compact()
    d2a, i2a = col.search(queries, k=10, r0=0.5, steps=8)
    d2b, i2b = col2.search(queries, k=10, r0=0.5, steps=8)
    np.testing.assert_array_equal(np.asarray(i2a), np.asarray(i2b))


def test_snapshot_restore_after_updates(setup, tmp_path):
    """The round-trip also holds for a mutated (inserted + tombstoned)
    index — the exact dynamic state is what persists."""
    data, queries, kb = setup
    col = Collection.create(
        "ck2", kb, data[:800], c=1.5, w0=3.6, t=32, k=10,
        policy=CompactionPolicy(auto=False),
    )
    col.add(data[800:1000])
    col.remove(np.arange(40, 80))
    d0, i0 = col.search(queries, k=10, r0=0.5, steps=8)
    col.snapshot(str(tmp_path))
    col2 = Collection.restore(str(tmp_path))
    assert col2.live_count() == col.live_count() == 960
    d1, i1 = col2.search(queries, k=10, r0=0.5, steps=8)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))


# ---------------------------------------------------------------------------
# Router: sharded surface + placement decision
# ---------------------------------------------------------------------------


def test_sharded_collection_matches_local(setup):
    """On a 1-shard mesh the ShardedCollection must agree exactly with a
    local index built from the same key (the merge is an identity)."""
    from repro.core import DBLSHParams, build

    data, queries, kb = setup
    mesh = make_mesh((1,), ("data",))
    params = DBLSHParams.derive(n=1200, d=16, c=1.5, w0=3.6, t=32, k=10)
    sc = ShardedCollection.create(
        "sh", kb, data, mesh, params=params, payload=np.arange(1200)
    )
    assert sc.n == 1200
    # exact mode pins tight numeric parity (the norm-form dot reduction
    # is re-associated per compiled program — DESIGN.md §7); the default
    # norm path pins id parity below through the service round trip.
    d_s, i_s = sc.search(queries, k=10, r0=0.5, steps=8, exact=True)

    local = build(kb, jnp.asarray(data), params)
    d_l, i_l = search_batch_fixed(
        local, jnp.asarray(queries), k=10, r0=0.5, steps=8, exact=True
    )
    np.testing.assert_array_equal(np.asarray(i_s), np.asarray(i_l))
    np.testing.assert_allclose(np.asarray(d_s), np.asarray(d_l), rtol=1e-6)
    # norm-form ids still agree with the local norm-form search
    _, i_sn = sc.search(queries, k=10, r0=0.5, steps=8)
    _, i_ln = search_batch_fixed(local, jnp.asarray(queries), k=10, r0=0.5, steps=8)
    np.testing.assert_array_equal(np.asarray(i_sn), np.asarray(i_ln))

    # the service serves a sharded collection through the same queue
    svc = StoreService(batch_shapes=(8,), default_k=10, r0=0.5, steps=8)
    svc.attach(sc)
    dd, ii, reqs = svc.serve("sh", queries[:8], k=10)
    np.testing.assert_array_equal(ii, np.asarray(i_ln[:8]))
    assert reqs[0].payload is not None


def test_open_collection_routing(setup):
    data, _, kb = setup
    mesh = make_mesh((1,), ("data",))
    col = open_collection("a", kb, data, mesh=None, c=1.5, w0=3.6, t=32, k=10)
    assert isinstance(col, Collection)
    # a 1-device mesh can never fan out
    col2 = open_collection(
        "b", kb, data, mesh=mesh, max_points_per_shard=100,
        c=1.5, w0=3.6, t=32, k=10,
    )
    assert isinstance(col2, Collection)


# ---------------------------------------------------------------------------
# Per-collection engine defaults + per-shard probe stats (ROADMAP items)
# ---------------------------------------------------------------------------


def test_collection_engine_default_resolution(setup, tmp_path):
    """Engine resolves request-override > collection default > service
    default; the default survives snapshot/restore; bad names reject."""
    data, queries, kb = setup
    col = Collection.create(
        "eng", kb, data, c=1.5, w0=3.6, t=32, k=10, engine="inline",
        inline_vectors=True,
    )
    assert col.default_engine == "inline"
    svc = StoreService(batch_shapes=(4,), default_k=10, r0=0.5, steps=8,
                       engine="jnp", interpret=True)
    svc.attach(col)

    # no override -> the collection's default engine
    r1 = svc.submit("eng", queries[0])
    assert r1.engine == "inline"
    # explicit override wins
    r2 = svc.submit("eng", queries[1], engine="jnp")
    assert r2.engine == "jnp"
    svc.flush()
    assert r1.done and r2.done

    # a collection without a default falls back to the service engine
    col2 = Collection.create("plain", kb, data, c=1.5, w0=3.6, t=32, k=10)
    assert col2.default_engine is None
    svc.attach(col2)
    assert svc.submit("plain", queries[2]).engine == "jnp"
    svc.flush()

    # mixed engines in one drained batch split into per-engine dispatches
    # but still serve every ticket
    reqs = [svc.submit("eng", q) for q in queries[3:5]]
    reqs.append(svc.submit("eng", queries[5], engine="jnp"))
    svc.flush()
    assert all(r.done for r in reqs)

    # validation reuses the core engine-name check
    with pytest.raises(ValueError):
        Collection.create("bad", kb, data, c=1.5, w0=3.6, t=32, k=10,
                          engine="vulkan")
    with pytest.raises(ValueError):
        svc.submit("eng", queries[0], engine="vulkan")
    # an inline default needs the inline layout — fail at create, not at
    # the first jitted dispatch
    with pytest.raises(ValueError):
        Collection.create("bad2", kb, data, c=1.5, w0=3.6, t=32, k=10,
                          engine="inline")

    # the default persists through snapshot/restore
    step = col.snapshot(str(tmp_path / "eng"))
    col3 = Collection.restore(str(tmp_path / "eng"), step)
    assert col3.default_engine == "inline"


def test_engine_default_results_match_explicit(setup):
    """A collection-default engine must produce the same results as the
    same engine passed explicitly (resolution changes routing only)."""
    data, queries, kb = setup
    col = Collection.create(
        "engeq", kb, data, c=1.5, w0=3.6, t=32, k=10, engine="kernel",
        inline_vectors=True,
    )
    d_def, i_def = col.search(queries[:4], k=10, r0=0.5, steps=8,
                              interpret=True)
    d_exp, i_exp = col.search(queries[:4], k=10, r0=0.5, steps=8,
                              engine="kernel", interpret=True)
    np.testing.assert_array_equal(np.asarray(d_def), np.asarray(d_exp))
    np.testing.assert_array_equal(np.asarray(i_def), np.asarray(i_exp))


def test_sharded_probe_stats_surface(setup):
    """Per-shard probe stats flow through the collective merge into
    svc.stats() instead of being dropped at the boundary: on a 1-shard
    mesh the aggregates equal the local collection's own stats."""
    data, queries, kb = setup
    mesh = make_mesh((1,), ("data",))
    from repro.core import DBLSHParams, build

    params = DBLSHParams.derive(n=1200, d=16, c=1.5, w0=3.6, t=32, k=10)
    sc = ShardedCollection.create("shstats", kb, data, mesh, params=params)
    d_s, i_s, st = sc.search(queries[:8], k=10, r0=0.5, steps=8,
                             with_stats=True)
    local = build(kb, jnp.asarray(data), params)
    *_, st_l = search_batch_fixed(local, jnp.asarray(queries[:8]), k=10,
                                  r0=0.5, steps=8, with_stats=True)
    np.testing.assert_array_equal(
        np.asarray(st["candidates"]), np.asarray(st_l["candidates"])
    )
    np.testing.assert_array_equal(
        np.asarray(st["radius_steps"]), np.asarray(st_l["radius_steps"])
    )

    # ...and the service-level snapshot reports them
    svc = StoreService(batch_shapes=(8,), default_k=10, r0=0.5, steps=8)
    svc.attach(sc)
    svc.serve("shstats", queries[:8], k=10)
    snap = svc.stats("shstats")
    assert snap["mean_candidates"] > 0
    assert 1 <= snap["mean_radius_steps"] <= 8

    # the sharded path ignores engine selection, so resolution pins its
    # fixed engine: overrides share one cache key and honest tickets
    r1 = svc.submit("shstats", queries[0], engine="kernel")
    svc.flush()
    assert r1.engine == "jnp"
    r2 = svc.submit("shstats", queries[0], engine="inline")
    svc.flush()
    assert r2.cached


# ---------------------------------------------------------------------------
# Quantized distance path through the collection lifecycle
# ---------------------------------------------------------------------------


def test_quant_collection_lifecycle(setup, tmp_path):
    """A quant_dtype collection keeps its quantized blocks consistent
    through search / add / remove / compact / snapshot / restore.

    The quantized blocks are *derived* state: snapshots persist only the
    fp32 truth and restore re-quantizes, so the roundtrip must be
    bit-identical (quantization is deterministic)."""
    data, queries, kb = setup
    k = 10
    col = Collection.create("q8", kb, data, c=1.5, w0=3.6, t=32, k=k,
                            quant_dtype="int8")
    d_fp, i_fp = col.search(queries, k=k, r0=0.5, steps=8)
    d_q, i_q = col.search(queries, k=k, r0=0.5, steps=8, dtype="int8")
    # documented band: the shortlist+re-rank loses a neighbor only when
    # it falls off its bin's 4k shortlist — recall within 0.005 of fp32
    assert _recall(i_q, i_fp, k) >= 0.995

    with pytest.raises(ValueError, match="quant_dtype"):
        col.search(queries, k=k, dtype="bf16")

    # mutations keep the quantized blocks slot-aligned
    rng = np.random.default_rng(3)
    new = rng.normal(size=(48, data.shape[1])).astype(np.float32) * 0.1
    ids = col.add(new)
    col.remove(np.asarray(ids)[:8])
    assert col.index.qvec_blocks.shape == col.index.vec_blocks.shape \
        if col.index.params.inline_vectors else True
    assert col.index.qvec_blocks.shape[:2] == col.index.ids_blocks.shape[:2]
    d_q2, i_q2 = col.search(queries, k=k, r0=0.5, steps=8, dtype="int8")
    d_f2, i_f2 = col.search(queries, k=k, r0=0.5, steps=8)
    assert _recall(i_q2, i_f2, k) >= 0.99

    # compaction rebuilds with the same quant_dtype
    col.compact()
    assert col.index.params.quant_dtype == "int8"
    assert col.index.qvec_blocks.shape[:2] == col.index.ids_blocks.shape[:2]

    # snapshot -> restore: re-quantization is bit-identical
    col.snapshot(str(tmp_path / "q8"))
    col2 = Collection.restore(str(tmp_path / "q8"))
    np.testing.assert_array_equal(
        np.asarray(col2.index.qvec_blocks), np.asarray(col.index.qvec_blocks)
    )
    np.testing.assert_array_equal(
        np.asarray(col2.index.qvec_scale), np.asarray(col.index.qvec_scale)
    )
    d_q3, i_q3 = col.search(queries, k=k, r0=0.5, steps=8, dtype="int8")
    d_q4, i_q4 = col2.search(queries, k=k, r0=0.5, steps=8, dtype="int8")
    np.testing.assert_array_equal(np.asarray(i_q3), np.asarray(i_q4))
    np.testing.assert_array_equal(np.asarray(d_q3), np.asarray(d_q4))


def test_quant_sharded_roundtrip(setup, tmp_path):
    """Sharded quant collections: per-shard shortlist + re-rank, and the
    bit-identical restore path rebuilds per-shard quantized blocks (ids
    are shard-local — a global re-quantize would read the wrong rows)."""
    data, queries, kb = setup
    ndev = len(jax.devices())
    mesh = make_mesh((ndev,), ("data",))
    k = 10
    sc = ShardedCollection.create("q8s", kb, data, mesh, c=1.5, w0=3.6,
                                  t=32, k=k, quant_dtype="int8")
    d_fp, i_fp = sc.search(queries, k=k, r0=0.5, steps=8)
    d_q, i_q = sc.search(queries, k=k, r0=0.5, steps=8, dtype="int8")
    assert _recall(i_q, i_fp, k) >= 0.99

    sc.snapshot(str(tmp_path / "q8s"))
    sc2 = ShardedCollection.restore(str(tmp_path / "q8s"), mesh=mesh)
    np.testing.assert_array_equal(
        np.asarray(sc2.sharded.index.qvec_blocks),
        np.asarray(sc.sharded.index.qvec_blocks),
    )
    d_q2, i_q2 = sc2.search(queries, k=k, r0=0.5, steps=8, dtype="int8")
    np.testing.assert_array_equal(np.asarray(i_q), np.asarray(i_q2))

    # migration (rebalancing-rebuild) restore keeps the quant path alive
    sc3 = ShardedCollection.restore(str(tmp_path / "q8s"), mesh=mesh,
                                    migrate=True)
    assert sc3.sharded.index.params.quant_dtype == "int8"
    d3f, i3f = sc3.search(queries, k=k, r0=0.5, steps=8)
    d3q, i3q = sc3.search(queries, k=k, r0=0.5, steps=8, dtype="int8")
    assert _recall(i3q, i3f, k) >= 0.99
