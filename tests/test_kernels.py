"""Per-kernel allclose tests vs the pure-jnp oracles (interpret mode).

Sweeps shapes/dtypes per the kernel contract; hypothesis drives extra
randomized shape cases.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.index import quantize_blocks
from repro.kernels import (
    candidate_dist,
    candidate_verify,
    fused_cand_search,
    fused_window_search,
    pairwise_l2,
    window_dist,
    window_verify,
)
from repro.kernels.ops import _quantize_query
from repro.kernels.ref import (
    candidate_dist_ref,
    candidate_verify_ref,
    fused_search_ref,
    pairwise_l2_ref,
    window_dist_ref,
    window_verify_ref,
)


def _mk_candidates(key, Q, C, K, d, n):
    ks = jax.random.split(key, 5)
    cand_proj = jax.random.normal(ks[0], (Q, C, K)) * 2.0
    cand_vecs = jax.random.normal(ks[1], (Q, C, d))
    cand_ids = jax.random.randint(ks[2], (Q, C), 0, n + 1)  # includes invalid n
    g = jax.random.normal(ks[3], (Q, K))
    q = jax.random.normal(ks[4], (Q, d))
    return cand_proj, cand_vecs, cand_ids, g, q


def _assert_topk_equal(got, ref, msg=""):
    """Top-k sets can permute among ties; compare distances exactly and
    ids as multisets bucketed by distance."""
    gd, gi = map(np.asarray, got)
    rd, ri = map(np.asarray, ref)
    np.testing.assert_allclose(gd, rd, rtol=1e-5, atol=1e-5, err_msg=msg)
    for qq in range(gd.shape[0]):
        finite = np.isfinite(rd[qq])
        assert set(gi[qq][finite]) == set(ri[qq][finite]), (msg, qq)


@pytest.mark.parametrize("Q,C,K,d,k", [
    (1, 64, 4, 16, 5),
    (3, 256, 12, 128, 50),
    (2, 100, 8, 33, 10),   # non-multiple C and odd d
    (4, 32, 2, 8, 32),     # k == C
])
def test_candidate_verify_matches_ref(Q, C, K, d, k):
    n = 1000
    args = _mk_candidates(jax.random.key(Q * C + d), Q, C, K, d, n)
    w = 2.5
    got = candidate_verify(*args, w, n=n, k=k, interpret=True)
    ref = candidate_verify_ref(*args, w, n, k)
    _assert_topk_equal(got, ref)


def test_candidate_verify_dedup():
    """Duplicate (id, dist) candidates must appear at most once in top-k."""
    Q, C, K, d, n, k = 1, 64, 4, 16, 100, 8
    cp, cv, ci, g, q = _mk_candidates(jax.random.key(0), Q, C, K, d, n)
    # force duplicates: same candidate repeated 8x, all guaranteed in-box
    cp = cp.at[:, :8, :].set(g[:, None, :])
    cv = cv.at[:, :8, :].set(0.5)
    ci = ci.at[:, :8].set(7)
    got_d, got_i = candidate_verify(cp, cv, ci, g, q, 100.0, n=n, k=k, interpret=True)
    ids = np.asarray(got_i)[0]
    finite = np.isfinite(np.asarray(got_d)[0])
    assert (ids[finite] == 7).sum() <= 1


def test_candidate_verify_all_masked():
    """w = 0 and far boxes -> empty result (+inf, id=n)."""
    Q, C, K, d, n, k = 2, 64, 4, 16, 50, 5
    cp, cv, ci, g, q = _mk_candidates(jax.random.key(1), Q, C, K, d, n)
    got_d, got_i = candidate_verify(cp + 100.0, cv, ci, g, q, 0.5, n=n, k=k,
                                    interpret=True)
    assert np.all(np.isinf(np.asarray(got_d)))
    assert np.all(np.asarray(got_i) == n)


@pytest.mark.parametrize("Q,M,nb,B,K,d,k", [
    (2, 4, 16, 32, 4, 16, 5),
    (1, 8, 8, 64, 12, 96, 20),  # M == nb
])
def test_window_verify_matches_ref(Q, M, nb, B, K, d, k):
    n = nb * B - 3
    ks = jax.random.split(jax.random.key(Q + M + nb), 6)
    proj_blocks = jax.random.normal(ks[0], (nb, B, K)) * 2.0
    vec_blocks = jax.random.normal(ks[1], (nb, B, d))
    # real tables hold each id at most once (ids >= n are padding slots)
    ids_blocks = jax.random.permutation(ks[2], nb * B).reshape(nb, B).astype(jnp.int32)
    # block ids include invalid sentinel nb
    blk_idx = jax.random.randint(ks[3], (Q, M), 0, nb + 1).astype(jnp.int32)
    g = jax.random.normal(ks[4], (Q, K))
    q = jax.random.normal(ks[5], (Q, d))
    w = 3.0
    got = window_verify(blk_idx, proj_blocks, vec_blocks, ids_blocks, g, q, w,
                        n=n, k=k, interpret=True)
    ref = window_verify_ref(blk_idx, proj_blocks, vec_blocks, ids_blocks, g, q,
                            w, n, k)
    # ref gathers duplicate blocks twice; kernel dedups identical pairs, so
    # compare distances only where both finite, and id-sets per query.
    _assert_topk_equal(got, ref)


@pytest.mark.parametrize("Q,L,Ct,K,d", [
    (2, 3, 64, 4, 16),
    (1, 5, 300, 12, 96),   # non-multiple Ct
    (4, 1, 32, 2, 8),
])
@pytest.mark.parametrize("exact", [False, True])
def test_candidate_dist_matches_ref(Q, L, Ct, K, d, exact):
    ks = jax.random.split(jax.random.key(Q * Ct + d), 4)
    cp = jax.random.normal(ks[0], (Q, L, Ct, K)) * 2.0
    cv = jax.random.normal(ks[1], (Q, L, Ct, d))
    cn = jnp.sum(jnp.square(cv), axis=-1)
    # sprinkle invalid slots: +inf proj / norm (padding contract)
    cp = cp.at[:, :, ::7, :].set(jnp.inf)
    cn = cn.at[:, :, ::7].set(jnp.inf)
    g = jax.random.normal(ks[2], (Q, L, K))
    q = jax.random.normal(ks[3], (Q, d))
    d2, hw = candidate_dist(cp, cv, cn, g, q, exact=exact, interpret=True)
    d2r, hwr = candidate_dist_ref(cp, cv, cn, g, q, exact=exact)
    np.testing.assert_allclose(np.asarray(hw), np.asarray(hwr), rtol=1e-6)
    # in exact mode invalid slots carry real (ignored) distances; the
    # contract masks them through hw, so compare where hw is finite
    mask = np.isfinite(np.asarray(hwr))
    np.testing.assert_allclose(
        np.asarray(d2)[mask], np.asarray(d2r)[mask], rtol=1e-4, atol=1e-4
    )
    if not exact:
        assert np.isinf(np.asarray(d2)[~np.isfinite(np.asarray(cn)).reshape(
            np.asarray(d2).shape)]).all()


@pytest.mark.parametrize("Q,L,M,nb,B,K,d", [
    (2, 2, 4, 16, 32, 4, 16),
    (1, 3, 8, 8, 64, 12, 96),   # M == nb
])
@pytest.mark.parametrize("exact", [False, True])
def test_window_dist_matches_ref(Q, L, M, nb, B, K, d, exact):
    ks = jax.random.split(jax.random.key(Q + M + nb + L), 6)
    lnb = L * nb
    proj_blocks = jax.random.normal(ks[0], (lnb, B, K)) * 2.0
    vec_blocks = jax.random.normal(ks[1], (lnb, B, d))
    norm_blocks = jnp.sum(jnp.square(vec_blocks), axis=-1)
    # tail padding: +inf proj/norm on the last block's back half
    proj_blocks = proj_blocks.at[-1, B // 2:, :].set(jnp.inf)
    norm_blocks = norm_blocks.at[-1, B // 2:].set(jnp.inf)
    # block ids include the invalid sentinel lnb
    blk_idx = jax.random.randint(ks[3], (Q, L * M), 0, lnb + 1).astype(jnp.int32)
    g = jax.random.normal(ks[4], (Q, L, K))
    q = jax.random.normal(ks[5], (Q, d))
    d2, hw = window_dist(blk_idx, proj_blocks, vec_blocks, norm_blocks, g, q,
                         M=M, exact=exact, interpret=True)
    d2r, hwr = window_dist_ref(blk_idx, proj_blocks, vec_blocks, norm_blocks,
                               g, q, M, exact=exact)
    np.testing.assert_allclose(np.asarray(hw), np.asarray(hwr), rtol=1e-6)
    mask = np.isfinite(np.asarray(hwr))
    np.testing.assert_allclose(
        np.asarray(d2)[mask], np.asarray(d2r)[mask], rtol=1e-4, atol=1e-4
    )
    # invalid block slots must be unadmittable at any radius
    invalid = np.asarray(blk_idx) >= lnb
    hw_slots = np.asarray(hw).reshape(Q, L * M, B)
    assert np.isinf(hw_slots[invalid]).all()


# --------------------------------------------------------------- fused search

def _halves(steps):
    """An ascending radius-schedule half-width ladder that straddles the
    typical hw distribution of unit-normal projections."""
    return jnp.asarray([0.4 * 1.5 ** j for j in range(steps)], jnp.float32)


def _mk_window(seed, L, M, nb, B, K, d):
    ks = jax.random.split(jax.random.key(seed), 5)
    lnb = L * nb
    n = lnb * B - 3
    data = jax.random.normal(ks[0], (n, d))
    # each table holds every slot id at most once; >= n slots are padding
    ids_blocks = jax.random.permutation(ks[1], lnb * B).reshape(lnb, B)
    ids_blocks = ids_blocks.astype(jnp.int32)
    vec_blocks = jnp.take(data, ids_blocks, axis=0, mode="fill", fill_value=0.0)
    norm_blocks = jnp.where(
        ids_blocks < n, jnp.sum(jnp.square(vec_blocks), axis=-1), jnp.inf
    )
    proj_blocks = jax.random.normal(ks[2], (lnb, B, K)) * 2.0
    proj_blocks = jnp.where(
        (ids_blocks < n)[..., None], proj_blocks, jnp.inf
    )
    return data, ids_blocks, vec_blocks, norm_blocks, proj_blocks, n, ks[3], ks[4]


def _assert_bins_equal(got, ref, n):
    """Bin accumulators: counts exact, distances allclose, ids as sets
    per (query, bin) over the finite entries (ties may permute)."""
    gd, gi, gc = map(np.asarray, got)
    rd, ri, rc = map(np.asarray, ref)
    np.testing.assert_array_equal(gc, rc)
    np.testing.assert_allclose(gd, rd, rtol=1e-5, atol=1e-5)
    Qn, steps, _ = gd.shape
    for qq in range(Qn):
        for j in range(steps):
            finite = np.isfinite(rd[qq, j])
            assert set(gi[qq, j][finite]) == set(ri[qq, j][finite]), (qq, j)


@pytest.mark.parametrize("Q,L,M,nb,B,K,d,ks", [
    (2, 2, 4, 8, 32, 4, 16, 5),
    (1, 3, 8, 8, 64, 12, 96, 20),   # M == nb
])
@pytest.mark.parametrize("steps", [1, 4, 8])
@pytest.mark.parametrize("mode", ["norm", "exact"])
def test_fused_window_search_matches_ref(Q, L, M, nb, B, K, d, ks, steps, mode):
    _, ids_blocks, vec_blocks, norm_blocks, proj_blocks, n, kb, kq = (
        _mk_window(Q + L * M + nb + steps, L, M, nb, B, K, d)
    )
    lnb = L * nb
    kb1, kb2 = jax.random.split(kb)
    # block ids include the invalid sentinel lnb
    blk_idx = jax.random.randint(kb1, (Q, L * M), 0, lnb + 1).astype(jnp.int32)
    g = jax.random.normal(kb2, (Q, L, K))
    q = jax.random.normal(kq, (Q, d))
    halves = _halves(steps)
    got = fused_window_search(
        blk_idx, halves, proj_blocks, vec_blocks, norm_blocks, ids_blocks,
        g, q, M=M, ks=ks, n=n, mode=mode, interpret=True,
    )
    d2r, hwr = window_dist_ref(blk_idx, proj_blocks, vec_blocks, norm_blocks,
                               g, q, M, exact=(mode == "exact"))
    idsr = jnp.take(ids_blocks, blk_idx, axis=0, mode="fill",
                    fill_value=n).reshape(Q, -1)
    ref = fused_search_ref(d2r, hwr, idsr, halves, n, ks)
    _assert_bins_equal(got, ref, n)


@pytest.mark.parametrize("Q,L,Ct,K,d,ks", [
    (2, 3, 64, 4, 16, 5),
    (1, 2, 300, 12, 96, 20),   # non-multiple Ct
])
@pytest.mark.parametrize("steps", [1, 6])
@pytest.mark.parametrize("mode", ["norm", "exact"])
def test_fused_cand_search_matches_ref(Q, L, Ct, K, d, ks, steps, mode):
    rks = jax.random.split(jax.random.key(Q * Ct + d + steps), 5)
    cp = jax.random.normal(rks[0], (Q, L, Ct, K)) * 2.0
    cv = jax.random.normal(rks[1], (Q, L, Ct, d))
    cn = jnp.sum(jnp.square(cv), axis=-1)
    n = 4096
    ci = jax.random.randint(rks[2], (Q, L, Ct), 0, n).astype(jnp.int32)
    # invalid slots: +inf proj / norm (gather-fill contract)
    cp = cp.at[:, :, ::7, :].set(jnp.inf)
    cn = cn.at[:, :, ::7].set(jnp.inf)
    g = jax.random.normal(rks[3], (Q, L, K))
    q = jax.random.normal(rks[4], (Q, d))
    halves = _halves(steps)
    got = fused_cand_search(cp, cv, cn, ci, halves, g, q, ks=ks, n=n,
                            mode=mode, tile_c=64, interpret=True)
    d2r, hwr = candidate_dist_ref(cp, cv, cn, g, q, exact=(mode == "exact"))
    # exact mode computes real distances on +inf-marked slots; the
    # contract masks them through hw alone, exactly like the kernel
    ref = fused_search_ref(d2r, hwr, ci.reshape(Q, -1), halves, n, ks)
    _assert_bins_equal(got, ref, n)


def _int8_pool(q, qb, qsc, norm_blocks, blk_idx):
    """The int8 kernel's (Q, S*B) d² pool: the same quantized dot,
    dequantized in the kernel's order."""
    qv, qqs = _quantize_query(q, "int8")
    xq = jnp.take(qb, blk_idx, axis=0, mode="fill", fill_value=0)
    xs = jnp.take(qsc, blk_idx, axis=0, mode="fill", fill_value=1.0)
    nrm = jnp.take(norm_blocks, blk_idx, axis=0, mode="fill", fill_value=jnp.inf)
    idot = jnp.einsum("qsbd,qd->qsb", xq.astype(jnp.int32),
                      qv.astype(jnp.int32)).astype(jnp.float32)
    q2 = jnp.sum(jnp.square(q), axis=-1)
    return jnp.maximum(
        nrm - 2.0 * (xs * qqs[:, :, None] * idot) + q2[:, None, None], 0.0
    ).reshape(q.shape[0], -1)


def _mk_bin_case(case):
    """A small inline layout with integer vectors (every distance is an
    exact integer in each mode, so ties are real) and one constant
    projection level per block: with g = 0 and halves j + 1/2 a block of
    level v lands whole in bin v (v = steps: never admitted).

    ``dup_tables``: both tables share one layout and levels, and query 0
    selects blocks 0 and 1 in each (identical (d², id) pairs).
    ``tied_dists``: every vector is stored twice, under two ids.
    ``bin_overflow``: every block in bin 1, so that bin takes the
    query's 40 admitted slots over five blocks.
    ``all_invalid``: query 1 selects no block at all."""
    L, M, nb, B, K, d, steps = 2, 3, 4, 8, 4, 16, 4
    lnb, n = L * nb, nb * B
    rng = np.random.default_rng(14)
    data = rng.integers(-3, 4, size=(n, d)).astype(np.float32)
    if case == "tied_dists":
        data[n // 2:] = data[: n // 2]
    perms = [rng.permutation(n) for _ in range(L)]
    levels = rng.integers(0, steps + 1, size=lnb)
    if case == "dup_tables":
        perms[1] = perms[0]
        levels[nb:] = levels[:nb]
    if case == "bin_overflow":
        levels[:] = 1
    ids_blocks = np.concatenate(perms).reshape(lnb, B).astype(np.int32)
    proj_blocks = np.broadcast_to(
        levels[:, None, None], (lnb, B, K)
    ).astype(np.float32)
    blk = np.stack([
        np.concatenate([rng.permutation(nb)[:M] + l * nb for l in range(L)])
        for _ in range(2)
    ]).astype(np.int32)
    blk[0, -1] = lnb  # one invalid slot
    if case == "dup_tables":
        blk[0] = [0, 1, 2, nb, nb + 1, lnb]
    if case == "all_invalid":
        blk[1] = lnb
    q = rng.integers(-3, 4, size=(2, d)).astype(np.float32)
    halves = np.arange(steps, dtype=np.float32) + 0.5
    data, ids_blocks = jnp.asarray(data), jnp.asarray(ids_blocks)
    vec_blocks = jnp.take(data, ids_blocks, axis=0)
    norm_blocks = jnp.sum(jnp.square(vec_blocks), axis=-1)
    return (data, ids_blocks, vec_blocks, norm_blocks, jnp.asarray(proj_blocks),
            jnp.asarray(blk), jnp.zeros((2, L, K)), jnp.asarray(q),
            jnp.asarray(halves), M, n)


@pytest.mark.parametrize("mode", ["norm", "exact", "int8", "bf16"])
@pytest.mark.parametrize(
    "case", ["dup_tables", "tied_dists", "bin_overflow", "all_invalid"]
)
def test_fused_window_bins_in_order(case, mode):
    """The bins hold each bin's ks smallest distinct (d², id) pairs in
    order, ties to the smaller id, duplicates once; cnt exact."""
    (data, ids_blocks, vec_blocks, norm_blocks, proj_blocks, blk, g, q,
     halves, M, n) = _mk_bin_case(case)
    quant = mode in ("int8", "bf16")
    ks = 4 * 3 if quant else 3
    xb, xs = (quantize_blocks(data, ids_blocks, mode) if quant
              else (vec_blocks, None))
    bd, bi, cnt = fused_window_search(
        blk, halves, proj_blocks, xb, norm_blocks, ids_blocks, g, q,
        M=M, ks=ks, n=n, mode=mode, interpret=True, x_scale=xs,
    )
    d2r, hwr = window_dist_ref(blk, proj_blocks, vec_blocks, norm_blocks,
                               g, q, M, exact=(mode == "exact"))
    if mode == "int8":
        d2r = _int8_pool(q, xb, xs, norm_blocks, blk)
    idsr = jnp.take(ids_blocks, blk, axis=0, mode="fill",
                    fill_value=n).reshape(2, -1)
    rd, ri, rc = fused_search_ref(d2r, hwr, idsr, halves, n, ks)
    np.testing.assert_array_equal(np.asarray(cnt), rc)
    np.testing.assert_array_equal(np.asarray(bi), ri)
    np.testing.assert_allclose(np.asarray(bd), rd, rtol=1e-5, atol=1e-5)
    # each case holds what it is named for
    if case == "dup_tables":
        adm = np.asarray(hwr[0]) < float(halves[-1])
        pairs = list(zip(np.asarray(d2r[0])[adm], np.asarray(idsr[0])[adm]))
        assert len(set(pairs)) < len(pairs)
    if case == "tied_dists":
        assert ((rd[:, :, 1:] == rd[:, :, :-1]) & np.isfinite(rd[:, :, 1:])).any()
    if case == "bin_overflow":
        assert rc[0, 1] > ks and np.isfinite(rd[0, 1]).all()
    if case == "all_invalid":
        assert (rc[1] == 0).all() and (ri[1] == n).all()


def test_fused_window_search_int8_matches_ref():
    """int8 mode: integer dots are exact, so the kernel must match a jnp
    oracle that replays the same quantized arithmetic (same scales, same
    dequant order) to fp32 rounding tolerance; admission counts stay
    fp32-exact."""
    Q, L, M, nb, B, K, d, ks, steps = 2, 2, 4, 8, 32, 4, 16, 8, 6
    data, ids_blocks, vec_blocks, norm_blocks, proj_blocks, n, kb, kq = (
        _mk_window(77, L, M, nb, B, K, d)
    )
    lnb = L * nb
    kb1, kb2 = jax.random.split(kb)
    blk_idx = jax.random.randint(kb1, (Q, L * M), 0, lnb + 1).astype(jnp.int32)
    g = jax.random.normal(kb2, (Q, L, K))
    q = jax.random.normal(kq, (Q, d))
    halves = _halves(steps)
    qb, qsc = quantize_blocks(data, ids_blocks, "int8")
    got = fused_window_search(
        blk_idx, halves, proj_blocks, qb, norm_blocks, ids_blocks,
        g, q, M=M, ks=ks, n=n, mode="int8", interpret=True, x_scale=qsc,
    )
    d2q = _int8_pool(q, qb, qsc, norm_blocks, blk_idx)
    _, hwr = window_dist_ref(blk_idx, proj_blocks, vec_blocks, norm_blocks,
                             g, q, M)
    idsr = jnp.take(ids_blocks, blk_idx, axis=0, mode="fill",
                    fill_value=n).reshape(Q, -1)
    ref = fused_search_ref(d2q, hwr, idsr, halves, n, ks)
    _assert_bins_equal(got, ref, n)


def test_fused_window_search_bf16_band():
    """bf16 mode: admission counts are fp32-exact (hw never quantizes),
    and the per-bin id sets stay within the documented recall band of
    the fp32 bins — reduced precision reorders near-ties only."""
    Q, L, M, nb, B, K, d, ks, steps = 2, 2, 4, 8, 32, 4, 24, 10, 6
    data, ids_blocks, vec_blocks, norm_blocks, proj_blocks, n, kb, kq = (
        _mk_window(99, L, M, nb, B, K, d)
    )
    lnb = L * nb
    kb1, kb2 = jax.random.split(kb)
    blk_idx = jax.random.randint(kb1, (Q, L * M), 0, lnb + 1).astype(jnp.int32)
    g = jax.random.normal(kb2, (Q, L, K))
    q = jax.random.normal(kq, (Q, d))
    halves = _halves(steps)
    qb, qsc = quantize_blocks(data, ids_blocks, "bf16")
    bd_q, bi_q, cnt_q = fused_window_search(
        blk_idx, halves, proj_blocks, qb, norm_blocks, ids_blocks,
        g, q, M=M, ks=ks, n=n, mode="bf16", interpret=True, x_scale=qsc,
    )
    bd_f, bi_f, cnt_f = fused_window_search(
        blk_idx, halves, proj_blocks, vec_blocks, norm_blocks, ids_blocks,
        g, q, M=M, ks=ks, n=n, mode="norm", interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(cnt_q), np.asarray(cnt_f))
    # documented tolerance band: per-bin id-set recall >= 0.9 vs fp32
    # (bf16 keeps ~8 mantissa bits — only near-ties at the shortlist
    # boundary may swap; bit-equality is NOT part of the contract)
    bi_qn, bi_fn = np.asarray(bi_q), np.asarray(bi_f)
    bd_fn = np.asarray(bd_f)
    hits = total = 0
    for qq in range(Q):
        for j in range(steps):
            want = set(bi_fn[qq, j][np.isfinite(bd_fn[qq, j])])
            have = set(bi_qn[qq, j].tolist())
            hits += len(want & have)
            total += len(want)
    assert total == 0 or hits / total >= 0.9, hits / total


def test_invalid_slots_never_contribute():
    """Satellite bugfix pin: an invalid select slot (blk >= lnb) must
    contribute nothing, even though its DMA is routed to block 0 and
    block 0 holds perfectly admittable points.  A clamp-style route to a
    *real* block with unmasked compute would leak block 0's points into
    every query that carries a padded slot."""
    L, M, nb, B, K, d = 1, 4, 4, 8, 4, 8
    lnb = L * nb
    n = lnb * B
    key = jax.random.key(5)
    k1, k2 = jax.random.split(key)
    q = jax.random.normal(k1, (1, d))
    g = jnp.zeros((1, L, K))
    # block 0: projections exactly at the query's g => hw = 0, always
    # admitted at any radius; vectors literally the query point
    proj_blocks = jnp.zeros((lnb, B, K))
    vec_blocks = jnp.broadcast_to(q[0], (lnb, B, d)).copy()
    norm_blocks = jnp.broadcast_to(jnp.sum(jnp.square(q)), (lnb, B)).copy()
    ids_blocks = jnp.arange(lnb * B, dtype=jnp.int32).reshape(lnb, B)
    all_invalid = jnp.full((1, L * M), lnb, jnp.int32)

    # window_dist: every slot must come back unadmittable (+inf)
    d2, hw = window_dist(all_invalid, proj_blocks, vec_blocks, norm_blocks,
                         g, q, M=M, interpret=True)
    assert np.isinf(np.asarray(hw)).all()
    assert np.isinf(np.asarray(d2)).all()

    # window_verify: empty result despite block 0 matching exactly
    vd, vi = window_verify(all_invalid[:, :M], proj_blocks, vec_blocks,
                           ids_blocks, g[:, 0], q, 100.0, n=n, k=5,
                           interpret=True)
    assert np.isinf(np.asarray(vd)).all()
    assert (np.asarray(vi) == n).all()

    # fused: all bins empty, zero admitted slots
    halves = _halves(4)
    bd, bi, cnt = fused_window_search(
        all_invalid, halves, proj_blocks, vec_blocks, norm_blocks,
        ids_blocks, g, q, M=M, ks=5, n=n, mode="norm", interpret=True,
    )
    assert np.isinf(np.asarray(bd)).all()
    assert (np.asarray(bi) == n).all()
    assert (np.asarray(cnt) == 0).all()

    # mixed: one valid slot -> exactly that block's points, nothing else
    mixed = jnp.asarray([[2, lnb, lnb, lnb]], jnp.int32)
    bd, bi, cnt = fused_window_search(
        mixed, halves, proj_blocks, vec_blocks, norm_blocks,
        ids_blocks, g, q, M=M, ks=B, n=n, mode="norm", interpret=True,
    )
    got_ids = set(np.asarray(bi)[np.isfinite(np.asarray(bd))].tolist())
    assert got_ids == set(np.asarray(ids_blocks[2]).tolist())
    assert int(np.asarray(cnt).sum()) == B


# ------------------------------------------------------- merge primitives

def test_merge_topk_duplicate_id_distinct_dists():
    """Dedup is on (dist, id) *pairs*: one id at two distances is two
    distinct candidates (the serving path never produces this — exact
    distances are a function of the id — but the primitive must not
    silently collapse them)."""
    from repro.kernels.window_verify import merge_topk
    cd = jnp.asarray([1.0, 2.0, 3.0, jnp.inf])
    ci = jnp.asarray([7, 7, 9, 0], jnp.int32)
    out_d = jnp.full((3,), jnp.inf)
    out_i = jnp.full((3,), np.iinfo(np.int32).max, jnp.int32)
    nd, ni = merge_topk(cd, ci, out_d, out_i, 3)
    np.testing.assert_allclose(np.asarray(nd), [1.0, 2.0, 3.0])
    np.testing.assert_array_equal(np.asarray(ni), [7, 7, 9])


def test_merge_topk_identical_pairs_dedup():
    """Cross-table duplicates carry identical (dist, id) pairs and must
    count once."""
    from repro.kernels.window_verify import merge_topk
    cd = jnp.asarray([2.0, 2.0, 2.0, 5.0])
    ci = jnp.asarray([4, 4, 4, 8], jnp.int32)
    out_d = jnp.full((3,), jnp.inf)
    out_i = jnp.full((3,), np.iinfo(np.int32).max, jnp.int32)
    nd, ni = merge_topk(cd, ci, out_d, out_i, 3)
    np.testing.assert_allclose(np.asarray(nd), [2.0, 5.0, jnp.inf])
    assert np.asarray(ni)[:2].tolist() == [4, 8]


@given(seed=st.integers(0, 2**31 - 1), k=st.integers(1, 12),
       a=st.integers(1, 16), b=st.integers(1, 24))
@settings(deadline=None, max_examples=15)
def test_merge_dedup_topk_property(seed, k, a, b):
    """Batched merge vs a host oracle: sorted distinct (dist, id) pairs,
    ascending, +inf/n padded — under duplicates, ties and all-inf tiles."""
    from repro.core import merge_dedup_topk
    rng = np.random.default_rng(seed)
    n = 64
    Qn = 3
    # coarse distance grid => plenty of exact ties; some ids duplicated
    run_d = np.sort(rng.choice([0.5, 1.0, 2.0, np.inf], (Qn, a)), axis=1)
    run_i = np.where(np.isfinite(run_d), rng.integers(0, n, (Qn, a)), n)
    new_d = rng.choice([0.25, 0.5, 1.0, 3.0, np.inf], (Qn, b))
    new_i = np.where(np.isfinite(new_d), rng.integers(0, n, (Qn, b)), n)
    if seed % 3 == 0:
        new_d[0, :] = np.inf  # an all-inf tile must be a no-op row
    gd, gi = merge_dedup_topk(
        jnp.asarray(run_d, jnp.float32), jnp.asarray(run_i, jnp.int32),
        jnp.asarray(new_d, jnp.float32), jnp.asarray(new_i, jnp.int32),
        n, k,
    )
    gd, gi = np.asarray(gd), np.asarray(gi)
    for qq in range(Qn):
        pairs = {
            (float(dd), int(ii))
            for dd, ii in zip(
                np.concatenate([run_d[qq], new_d[qq]]),
                np.concatenate([run_i[qq], new_i[qq]]),
            )
            if np.isfinite(dd)
        }
        want = sorted(pairs)[:k]
        want_d = [p[0] for p in want] + [np.inf] * (k - len(want))
        want_i = [p[1] for p in want] + [n] * (k - len(want))
        np.testing.assert_allclose(gd[qq], want_d)
        np.testing.assert_array_equal(gi[qq], want_i)


def test_merge_dedup_topk_tie_overflow():
    """More than k candidates at one distance: the k smallest ids win,
    in id order (the lexicographic (dist, id) contract)."""
    from repro.core import merge_dedup_topk
    n, k = 100, 4
    run_d = jnp.full((1, k), jnp.inf)
    run_i = jnp.full((1, k), n, jnp.int32)
    new_d = jnp.full((1, 8), 2.0)
    new_i = jnp.asarray([[31, 3, 55, 14, 90, 2, 77, 41]], jnp.int32)
    gd, gi = merge_dedup_topk(run_d, run_i, new_d, new_i, n, k)
    np.testing.assert_allclose(np.asarray(gd)[0], [2.0] * k)
    np.testing.assert_array_equal(np.asarray(gi)[0], [2, 3, 14, 31])


@pytest.mark.parametrize("nq,nn,d", [
    (8, 16, 8),
    (256, 512, 128),
    (100, 300, 65),      # ragged everything
    (1, 1000, 960),      # gist-shaped
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_l2_matches_ref(nq, nn, d, dtype):
    kq, kx = jax.random.split(jax.random.key(nq + nn))
    Q = jax.random.normal(kq, (nq, d), dtype)
    X = jax.random.normal(kx, (nn, d), dtype)
    got = pairwise_l2(Q, X, interpret=True)
    ref = pairwise_l2_ref(Q.astype(jnp.float32), X.astype(jnp.float32))
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=tol,
                               atol=tol * d)


@given(
    nq=st.integers(1, 40),
    nn=st.integers(1, 80),
    d=st.integers(1, 70),
)
@settings(deadline=None, max_examples=10)
def test_pairwise_l2_property(nq, nn, d):
    kq, kx = jax.random.split(jax.random.key(nq * 7919 + nn * 31 + d))
    Q = jax.random.normal(kq, (nq, d))
    X = jax.random.normal(kx, (nn, d))
    got = pairwise_l2(Q, X, tile_q=16, tile_n=16, tile_d=32, interpret=True)
    ref = pairwise_l2_ref(Q, X)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4,
                               atol=2e-3)


def test_pairwise_l2_self_distance_zero():
    X = jax.random.normal(jax.random.key(3), (64, 32))
    got = np.asarray(pairwise_l2(X, X, interpret=True))
    assert np.all(np.abs(np.diag(got)) < 1e-3)
