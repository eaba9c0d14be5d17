"""Incremental index maintenance: insert / delete / compact invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import DBLSHParams, brute_force, build, search_batch_fixed
from repro.core.updates import compact, delete, insert, live_count
from repro.data import make_clustered, normalize_scale


@pytest.fixture(scope="module")
def setup():
    kd, kb = jax.random.split(jax.random.key(21))
    allpts = make_clustered(kd, 3096, 24, n_clusters=12, spread=0.02)
    data, extra, queries = allpts[:2000], allpts[2000:3064], allpts[3064:]
    data, queries, scale = normalize_scale(data, queries)
    extra = extra * scale
    params = DBLSHParams.derive(n=2000, d=24, c=1.5, t=48, k=10, K=8, L=3)
    index = build(kb, data, params)
    return data, extra, queries, index


def _recall(index, data, queries, k=10):
    _, ids = search_batch_fixed(index, queries, k=k, r0=0.5, steps=8)
    _, gt = brute_force(data, queries, k=k)
    return np.mean(
        [len(set(a.tolist()) & set(b.tolist())) / k
         for a, b in zip(np.asarray(ids), np.asarray(gt))]
    )


def test_insert_points_found(setup):
    data, extra, queries, index = setup
    idx2 = insert(index, extra)
    assert idx2.n == 2000 + extra.shape[0]
    full = jnp.concatenate([data, extra])
    rec = _recall(idx2, full, queries)
    assert rec > 0.6, rec
    # query placed exactly on an inserted point must return it; the
    # self-distance check needs exact=True — the MXU norm form's
    # ||x||^2 - 2<q,x> + ||q||^2 cancellation floor is O(eps * ||x||^2),
    # far above 1e-3 at this coordinate scale (DESIGN.md §7)
    q = extra[7:8]
    d, i = search_batch_fixed(idx2, q, k=1, r0=0.25, steps=8)
    assert int(i[0, 0]) == 2000 + 7
    d, i = search_batch_fixed(idx2, q, k=1, r0=0.25, steps=8, exact=True)
    assert int(i[0, 0]) == 2000 + 7
    assert float(d[0, 0]) < 1e-3


def test_insert_preserves_old_points(setup):
    data, extra, queries, index = setup
    idx2 = insert(index, extra)
    rec_old = _recall(index, data, queries)
    # recall against the OLD ground truth barely moves (new points can
    # legitimately enter true top-k; compare on old-gt membership)
    _, ids2 = search_batch_fixed(idx2, queries, k=10, r0=0.5, steps=8)
    _, gt_old = brute_force(data, queries, k=10)
    # every old-gt point that idx2 misses must be displaced by a closer new point
    full = jnp.concatenate([data, extra])
    d_full, _ = brute_force(full, queries, k=10)
    rec2 = _recall(idx2, full, queries)
    assert rec2 >= rec_old - 0.15


def test_delete_never_returned(setup):
    data, extra, queries, index = setup
    _, gt = brute_force(data, queries, k=5)
    victims = jnp.unique(gt.reshape(-1))[:50]  # delete many true NNs
    idx2 = delete(index, victims)
    assert live_count(idx2) == 2000 - int(victims.shape[0])
    _, ids = search_batch_fixed(idx2, queries, k=10, r0=0.5, steps=8)
    bad = set(np.asarray(victims).tolist()) & set(np.asarray(ids).reshape(-1).tolist())
    assert not bad, bad


def test_compact_after_delete(setup):
    data, extra, queries, index = setup
    victims = jnp.arange(0, 500, dtype=jnp.int32)
    idx2 = delete(index, victims)
    idx3, id_map = compact(idx2, jax.random.key(5))
    assert idx3.n == 1500
    assert int(jnp.sum(id_map >= 0)) == 1500
    assert np.all(np.asarray(id_map[:500]) == -1)
    # surviving data rows preserved under the id map
    survivors = np.asarray(id_map[500:])
    np.testing.assert_allclose(
        np.asarray(idx3.data)[survivors], np.asarray(data)[500:], rtol=1e-6
    )
    # search works and never returns pre-compact ids >= 1500
    _, ids = search_batch_fixed(idx3, queries, k=5, r0=0.5, steps=8)
    assert np.asarray(ids).max() <= 1500


@given(seed=st.integers(0, 10_000))
@settings(deadline=None, max_examples=4)
def test_update_roundtrip_vs_brute_force(setup, seed):
    """Property: insert -> delete -> compact round-trips against a
    brute-force scan of the surviving point set — deleted ids are never
    returned, surviving inserted points stay findable under the id map,
    and live_count tracks every transition."""
    data, extra, queries, index = setup
    rng = np.random.default_rng(seed)
    n0 = 2000
    m = int(rng.integers(16, 96))
    ins = extra[:m]
    n_tot = n0 + m

    idx2 = insert(index, ins)
    assert live_count(idx2) == n_tot

    n_del = int(rng.integers(10, 200))
    del_ids = rng.choice(n_tot, size=n_del, replace=False).astype(np.int32)
    idx3 = delete(idx2, jnp.asarray(del_ids))
    assert live_count(idx3) == n_tot - n_del

    # deleted ids can never be returned, even pre-compaction
    _, ids = search_batch_fixed(idx3, queries, k=10, r0=0.5, steps=8)
    leaked = set(del_ids.tolist()) & set(np.asarray(ids).reshape(-1).tolist())
    assert not leaked, leaked

    idx4, id_map = compact(idx3, jax.random.key(seed))
    id_map = np.asarray(id_map)
    assert idx4.n == n_tot - n_del
    assert live_count(idx4) == idx4.n

    # the compacted data is exactly the brute-force surviving scan
    full = np.concatenate([np.asarray(data), np.asarray(ins)])
    live_mask = np.ones(n_tot, bool)
    live_mask[del_ids] = False
    np.testing.assert_allclose(
        np.asarray(idx4.data), full[live_mask], rtol=1e-6
    )
    assert np.all(id_map[~live_mask] == -1)
    assert np.array_equal(np.sort(id_map[live_mask]), np.arange(idx4.n))

    # a surviving inserted point is findable at its remapped id
    surviving_ins = np.flatnonzero(live_mask[n0:]) + n0
    if surviving_ins.size:
        old_id = int(surviving_ins[0])
        d, i2 = search_batch_fixed(
            idx4, jnp.asarray(full[old_id][None]), k=1, r0=0.25, steps=8,
            exact=True,  # self-distance sits below the norm-form fp floor
        )
        assert int(i2[0, 0]) == int(id_map[old_id])
        assert float(d[0, 0]) < 1e-3


@given(m=st.integers(1, 130))
@settings(deadline=None, max_examples=8)
def test_insert_partition_invariant(setup, m):
    """Every id 0..n+m-1 appears exactly once per table after insert."""
    data, extra, queries, index = setup
    idx2 = insert(index, extra[:m])
    n_total = 2000 + m
    ids = np.asarray(idx2.ids_blocks[0]).reshape(-1)
    real = ids[ids < n_total]
    assert sorted(real.tolist()) == list(range(n_total))
