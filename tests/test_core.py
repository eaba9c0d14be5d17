"""Unit + property tests for the DB-LSH core (paper §III-V)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    C2Index,
    DBLSHParams,
    FBLSH,
    MQIndex,
    alpha_of_gamma,
    brute_force,
    build,
    collision_prob,
    rho_star,
    search_batch,
)
from repro.data import make_clustered, normalize_scale


# ---------------------------------------------------------------------------
# hashing / params theory
# ---------------------------------------------------------------------------


def test_alpha_headline_constant():
    """Lemma 3: alpha = 4.746 at gamma = 2 (w0 = 4 c^2)."""
    assert abs(alpha_of_gamma(2.0) - 4.746) < 2e-3


def test_alpha_monotone_and_threshold():
    """xi is increasing; xi(gamma) > 1 iff gamma > 0.7518 (paper §V-B)."""
    gs = np.linspace(0.2, 4.0, 100)
    vals = [alpha_of_gamma(g) for g in gs]
    assert all(b > a for a, b in zip(vals, vals[1:]))
    assert alpha_of_gamma(0.752) > 1.0 > alpha_of_gamma(0.751)


@given(
    c=st.floats(1.05, 4.0),
    gamma=st.floats(0.8, 3.0),
)
@settings(deadline=None, max_examples=25)
def test_rho_star_bound(c, gamma):
    """Lemma 3: rho* <= 1/c^alpha for w0 = 2 gamma c^2 (log space, since
    rho* underflows float64 for very wide buckets)."""
    import math as _m

    from repro.core.params import log_rho_star

    w0 = 2.0 * gamma * c * c
    alpha = alpha_of_gamma(gamma)
    log_rs = log_rho_star(c, w0)
    assert log_rs <= -alpha * _m.log(c) + 1e-9
    assert log_rs < 0.0  # rho* < 1


def test_collision_prob_monte_carlo():
    """Eq. 4 closed form vs Monte-Carlo simulation of h(o) = a.o."""
    key = jax.random.key(0)
    d, trials = 64, 200_000
    o1 = jnp.zeros((d,))
    for tau, w in [(1.0, 4.0), (2.0, 4.0), (1.0, 9.0), (3.0, 9.0)]:
        o2 = o1.at[0].set(tau)  # distance tau
        a = jax.random.normal(key, (trials, d))
        emp = jnp.mean(jnp.abs(a @ (o1 - o2)) <= w / 2)
        closed = collision_prob(tau, w)
        assert abs(float(emp) - float(closed)) < 5e-3, (tau, w)


def test_observation1_radius_invariance():
    """Observation 1: p(r; w0 r) = p(1; w0) for any r."""
    for r in [0.5, 1.0, 3.0, 17.0]:
        assert abs(
            float(collision_prob(r, 9.0 * r)) - float(collision_prob(1.0, 9.0))
        ) < 1e-6


def test_params_derivation():
    p = DBLSHParams.derive(n=100_000, d=128, c=1.5, t=100, k=50)
    # K = ceil(log_{1/p2}(n/t)), L = ceil((n/t)^rho)
    assert p.K == math.ceil(math.log(p.n / p.t) / math.log(1.0 / p.p2))
    assert p.L == math.ceil((p.n / p.t) ** p.rho)
    assert p.p1 > p.p2
    assert p.budget == 2 * p.t * p.L + p.k
    assert p.cand_per_table >= 2 * p.t + p.k


# ---------------------------------------------------------------------------
# index structure invariants
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_setup():
    key = jax.random.key(7)
    kd, kb = jax.random.split(key)
    # paper §VI-A: queries are drawn from the dataset and removed from it.
    allpts = make_clustered(kd, 4032, 32, n_clusters=16, spread=0.02)
    data, queries = allpts[:4000], allpts[4000:]
    data, queries, _ = normalize_scale(data, queries)
    params = DBLSHParams.derive(n=4000, d=32, c=1.5, t=64, k=10, K=10, L=4)
    index = build(kb, data, params)
    return data, queries, params, index


def test_index_partition(small_setup):
    """Every point id appears exactly once per table; MBRs contain their
    block's points."""
    data, _, params, index = small_setup
    n = data.shape[0]
    ids = np.asarray(index.ids_blocks)  # (L, nb, B)
    for l_ in range(params.L):
        flat = ids[l_].reshape(-1)
        real = flat[flat < n]
        assert sorted(real.tolist()) == list(range(n))
    pb = np.asarray(index.proj_blocks)
    lo = np.asarray(index.mbr_lo)[:, :, None, :]
    hi = np.asarray(index.mbr_hi)[:, :, None, :]
    finite = np.isfinite(pb)
    assert np.all((pb >= lo) | ~finite)
    assert np.all((pb <= hi) | ~finite)


def test_index_projection_consistency(small_setup):
    """proj_blocks really are G_i(o) of the stored ids."""
    data, _, params, index = small_setup
    n = data.shape[0]
    l_ = 0
    ids = np.asarray(index.ids_blocks[l_]).reshape(-1)
    pb = np.asarray(index.proj_blocks[l_]).reshape(-1, params.K)
    A = np.asarray(index.proj_vecs[l_])  # (K, d)
    mask = ids < n
    expect = np.asarray(data)[ids[mask]] @ A.T
    np.testing.assert_allclose(pb[mask], expect, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# query correctness
# ---------------------------------------------------------------------------


def test_search_finds_exact_nn_mostly(small_setup):
    """Theorem 1: success probability >= 1/2 - 1/e ~ 0.13 for c^2-ANN.
    In practice recall is far higher; assert a conservative floor."""
    data, queries, params, index = small_setup
    k = 10
    dists, ids = search_batch(index, queries, k=k, r0=0.5)
    gt_d, gt_i = brute_force(data, queries, k=k)
    recall = np.mean(
        [len(set(np.asarray(a)) & set(np.asarray(b))) / k for a, b in zip(ids, gt_i)]
    )
    assert recall > 0.5, recall
    # returned distances are genuine distances of returned ids
    got = np.asarray(dists)
    for qi in range(queries.shape[0]):
        valid = np.asarray(ids[qi]) < data.shape[0]
        real = np.linalg.norm(
            np.asarray(data)[np.asarray(ids[qi])[valid]] - np.asarray(queries[qi]),
            axis=-1,
        )
        np.testing.assert_allclose(got[qi][valid], real, rtol=1e-3, atol=1e-3)
    # results sorted ascending
    assert np.all(np.diff(got, axis=-1) >= -1e-6)


def test_c2ann_guarantee(small_setup):
    """Every returned 1-NN is a c^2-approximate NN with prob >> 1/2 - 1/e.
    We assert the *aggregate* guarantee: >= 80% of queries satisfy
    ||q,o|| <= c^2 ||q,o*|| (theory floor is 13.2%)."""
    data, queries, params, index = small_setup
    dists, ids = search_batch(index, queries, k=1, r0=0.5)
    gt_d, _ = brute_force(data, queries, k=1)
    ratio = np.asarray(dists[:, 0]) / np.maximum(np.asarray(gt_d[:, 0]), 1e-9)
    frac_ok = np.mean(ratio <= params.c**2 + 1e-3)
    assert frac_ok >= 0.8, (frac_ok, ratio)


def test_rc_nn_semantics(small_setup):
    """(r,c)-NN (Def. 2): when it returns a point at radius r covering the
    true NN, the point's distance must be <= c*r (case 1)."""
    from repro.core import rc_nn

    data, queries, params, index = small_setup
    gt_d, _ = brute_force(data, queries, k=1)
    q = queries[0]
    r_star = float(gt_d[0, 0])
    r = 2.0 * r_star  # true NN well within radius
    d, i = rc_nn(index, q, r=r, k=1)
    # E1 holds w.h.p.: a point should be found, and then it must be valid
    if np.isfinite(np.asarray(d)[0]):
        assert float(d[0]) <= params.c * r * (1 + 1e-3)


@given(seed=st.integers(0, 2**31 - 1))
@settings(deadline=None, max_examples=5)
def test_property_results_are_valid_points(small_setup, seed):
    """Property: any finite returned (dist, id) is consistent — id in range
    and dist equals the true distance."""
    data, _, params, index = small_setup
    q = jax.random.normal(jax.random.key(seed), (data.shape[1],)) * 0.5
    d, i = search_batch(index, q[None, :], k=5, r0=0.5)
    d, i = np.asarray(d)[0], np.asarray(i)[0]
    for dist, idx in zip(d, i):
        if np.isfinite(dist):
            assert 0 <= idx < data.shape[0]
            true = np.linalg.norm(np.asarray(data)[idx] - np.asarray(q))
            assert abs(true - dist) < 1e-2 * max(1.0, true)


# ---------------------------------------------------------------------------
# baselines sanity
# ---------------------------------------------------------------------------


def test_brute_force_is_exact(small_setup):
    data, queries, _, _ = small_setup
    d, i = brute_force(data, queries, k=5)
    dn = np.asarray(data)
    for qi in range(4):
        ref = np.sort(np.linalg.norm(dn - np.asarray(queries[qi]), axis=-1))[:5]
        # rank-1 matmul formulation costs ~1e-3 fp32 ulp vs direct norms
        np.testing.assert_allclose(np.asarray(d[qi]), ref, rtol=2e-3, atol=2e-3)


def test_baselines_reasonable_recall(small_setup):
    data, queries, params, _ = small_setup
    k = 10
    _, gt = brute_force(data, queries, k=k)
    gt = np.asarray(gt)

    mq = MQIndex.build(jax.random.key(1), data, m=15, beta=0.08)
    _, ids = mq.search_batch(queries, k=k)
    rec_mq = np.mean([len(set(a.tolist()) & set(b.tolist())) / k for a, b in zip(np.asarray(ids), gt)])
    assert rec_mq > 0.5, rec_mq

    c2 = C2Index.build(jax.random.key(2), data, m=40, w=2.0)
    _, ids = c2.search_batch(queries, k=k)
    rec_c2 = np.mean([len(set(a.tolist()) & set(b.tolist())) / k for a, b in zip(np.asarray(ids), gt)])
    assert rec_c2 > 0.3, rec_c2

    fb = FBLSH.build(jax.random.key(3), data, K=8, L=4, w0=params.w0, c=1.5, t=32)
    _, ids = fb.search_batch(queries, k=k, r0=0.5)
    rec_fb = np.mean([len(set(a.tolist()) & set(b.tolist())) / k for a, b in zip(np.asarray(ids), gt)])
    assert rec_fb > 0.2, rec_fb


def test_inline_matches_gather_layout(small_setup):
    """'inline' (streaming) and 'gather' layouts return identical results."""
    import dataclasses as dc

    data, queries, params, index = small_setup
    p2 = dc.replace(params, inline_vectors=True)
    index2 = build(jax.random.key(7 + 0), data, p2)  # different key -> rebuild
    # rebuild gather index with same key for apples-to-apples
    kb = jax.random.split(jax.random.key(42), 1)[0]
    ia = build(kb, data, params)
    ib = build(kb, data, p2)
    da, ia_ = search_batch(ia, queries[:8], k=5, r0=0.5)
    db, ib_ = search_batch(ib, queries[:8], k=5, r0=0.5)
    np.testing.assert_allclose(np.asarray(da), np.asarray(db), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(ia_), np.asarray(ib_))
