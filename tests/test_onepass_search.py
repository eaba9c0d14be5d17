"""Equivalence suite for the one-pass incremental probe pipeline.

The rebuilt ``search_batch_fixed`` selects blocks once at the final
radius, verifies every selected slot once, and replays the radius
schedule as masks over per-slot window halfwidths (DESIGN.md §7).  The
multi-pass seed algorithm is preserved verbatim as
``search_batch_fixed_ref``; this suite pins:

* **new-vs-ref parity** across the engine matrix
  (``REPRO_STORE_TEST_ENGINES``) and ``steps ∈ {1, 4, 8}`` — id-set
  equality and recall parity (distances only to norm-form tolerance);
* **exact bit-equality** — with ``exact=True`` (diff-form distances)
  and an untruncated block budget, the one-pass path returns
  bit-identical distances to the seed path;
* **the nesting contract** (property) — after each step j the
  incremental state equals a from-scratch probe at radius c^j·r0
  (``query.probe_radius`` is the independent oracle);
* **distinct candidate accounting** — the one-pass ``candidates`` stat
  counts every fetched slot once (vs the seed's per-step recount) and
  never counts padded selection slots.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st
from repro.core import (
    DBLSHParams,
    brute_force,
    build,
    merge_dedup_topk,
    probe_radius,
    search_batch_fixed,
    search_batch_fixed_ref,
)
from repro.data import make_clustered, normalize_scale

ENGINES = os.environ.get(
    "REPRO_STORE_TEST_ENGINES", "jnp kernel inline"
).replace(",", " ").split()

K_TEST = 8


@pytest.fixture(scope="module")
def setup():
    key = jax.random.key(29)
    kd, kb = jax.random.split(key)
    allpts = make_clustered(kd, 2080, 24, n_clusters=12, spread=0.02)
    data, queries = allpts[:2048], allpts[2048:]
    data, queries, _ = normalize_scale(data, queries)
    # max_blocks == nb: the fixed capacity never truncates, so the
    # one-pass and multi-pass paths see identical candidate sets and the
    # equality assertions are exact rather than statistical.
    params = DBLSHParams.derive(
        n=2048, d=24, c=1.5, t=48, k=10, K=8, L=3,
        inline_vectors=True, max_blocks=32,
    )
    index = build(kb, data, params)
    assert params.max_blocks == index.nb
    return np.asarray(data), jnp.asarray(queries), index


def _idsets_equal(d_a, i_a, d_b, i_b):
    d_a, i_a, d_b, i_b = map(np.asarray, (d_a, i_a, d_b, i_b))
    for q in range(d_a.shape[0]):
        fa, fb = np.isfinite(d_a[q]), np.isfinite(d_b[q])
        if set(i_a[q][fa]) != set(i_b[q][fb]):
            return False
    return True


@pytest.mark.parametrize("steps", [1, 4, 8])
@pytest.mark.parametrize("engine", ENGINES)
def test_new_vs_ref_parity(setup, engine, steps):
    """One-pass vs seed: identical id sets, recall parity, distances to
    norm-form tolerance, for every engine and schedule length."""
    data, queries, index = setup
    d_ref, i_ref = search_batch_fixed_ref(
        index, queries, k=K_TEST, r0=0.5, steps=steps, engine="jnp"
    )
    d_new, i_new = search_batch_fixed(
        index, queries, k=K_TEST, r0=0.5, steps=steps, engine=engine,
        interpret=True,
    )
    assert _idsets_equal(d_ref, i_ref, d_new, i_new)
    np.testing.assert_allclose(
        np.asarray(d_new), np.asarray(d_ref), rtol=1e-2, atol=1e-2
    )

    _, gt_i = brute_force(jnp.asarray(data), queries, k=K_TEST)
    rec = lambda ids: np.mean([
        len(set(a.tolist()) & set(b.tolist())) / K_TEST
        for a, b in zip(np.asarray(ids), np.asarray(gt_i))
    ])
    assert abs(rec(i_new) - rec(i_ref)) <= 0.005 + 1e-9


@pytest.mark.parametrize("engine", ENGINES)
def test_exact_bit_equality_to_seed(setup, engine):
    """exact=True restores diff-form distances: bit-equal to the seed
    path (the unit the ISSUE pins for the fp-rounding escape hatch)."""
    data, queries, index = setup
    for steps in (1, 4, 8):
        d_ref, i_ref = search_batch_fixed_ref(
            index, queries, k=K_TEST, r0=0.5, steps=steps, engine="jnp"
        )
        d_new, i_new = search_batch_fixed(
            index, queries, k=K_TEST, r0=0.5, steps=steps, engine=engine,
            interpret=True, exact=True,
        )
        np.testing.assert_array_equal(np.asarray(d_new), np.asarray(d_ref))
        assert _idsets_equal(d_ref, i_ref, d_new, i_new)


@given(steps=st.integers(1, 6), r0_scale=st.integers(2, 8))
@settings(deadline=None, max_examples=6)
def test_nesting_contract_property(setup, steps, r0_scale):
    """Property: incremental per-step results equal from-scratch probes
    at the same radius.

    The oracle rebuilds each step from first principles with
    ``query.probe_radius`` (an independent single-query window probe at
    one width) and the same masked-merge/termination rule; windows nest,
    so replaying deltas over one final-radius selection must land in the
    same state after every step."""
    data, queries, index = setup
    p = index.params
    r0 = r0_scale / 10.0
    n = index.n
    nq = 8
    Q = queries[:nq]
    k = K_TEST

    d_new, i_new = search_batch_fixed(
        index, Q, k=k, r0=r0, steps=steps, exact=True
    )

    # from-scratch oracle: full window probe per (query, step)
    G = jnp.einsum("lkd,qd->qlk", index.proj_vecs, Q)
    best_d = jnp.full((nq, k), jnp.inf)
    best_i = jnp.full((nq, k), n, jnp.int32)
    done = np.zeros((nq,), bool)
    r = jnp.asarray(r0, jnp.float32)
    for _ in range(steps):
        w = p.w0 * r
        d2s, idss = [], []
        for qi in range(nq):
            d2, ids = probe_radius(index, Q[qi], G[qi], w)
            d2s.append(d2)
            idss.append(ids)
        nd, ni = merge_dedup_topk(
            best_d, best_i, jnp.stack(d2s), jnp.stack(idss), n, k
        )
        best_d = jnp.where(jnp.asarray(done)[:, None], best_d, nd)
        best_i = jnp.where(jnp.asarray(done)[:, None], best_i, ni)
        done = done | np.asarray(best_d[:, k - 1] <= jnp.square(p.c * r))
        r = r * p.c

    # ulp-level tolerance: the oracle reduces per query over (M, B, d)
    # while the pipeline reduces the batched (Qn, S, B, d) pool — XLA may
    # re-associate the last-axis sum differently per shape
    np.testing.assert_allclose(
        np.asarray(d_new), np.asarray(jnp.sqrt(best_d)), rtol=0, atol=5e-7
    )
    assert _idsets_equal(d_new, i_new, jnp.sqrt(best_d), best_i)


def test_distinct_candidate_accounting(setup):
    """The rebuilt ``candidates`` stat counts each fetched slot once:
    monotone non-decreasing in steps, equal to the seed count at steps=1,
    and strictly below the seed's per-step recount once windows nest."""
    data, queries, index = setup
    B = index.params.block_size
    prev = None
    for steps in (1, 4, 8):
        *_, s_new = search_batch_fixed(
            index, queries, k=K_TEST, r0=0.5, steps=steps, with_stats=True
        )
        *_, s_ref = search_batch_fixed_ref(
            index, queries, k=K_TEST, r0=0.5, steps=steps, with_stats=True
        )
        c_new = np.asarray(s_new["candidates"])
        c_ref = np.asarray(s_ref["candidates"])
        assert (c_new % B == 0).all()  # whole blocks, no padded slots
        if steps == 1:
            # a single radius has no re-fetch to dedup: counts agree
            np.testing.assert_array_equal(c_new, c_ref)
        else:
            assert (c_new <= c_ref).all()
            assert c_new.sum() < c_ref.sum()
        # distinct slots only grow as the schedule lengthens
        if prev is not None:
            assert (c_new >= prev).all()
        prev = c_new
        np.testing.assert_array_equal(
            np.asarray(s_new["radius_steps"]), np.asarray(s_ref["radius_steps"])
        )


@pytest.mark.parametrize("k", [1, 25])
@pytest.mark.parametrize("exact", [False, True])
@pytest.mark.parametrize("engine", [e for e in ENGINES if e != "jnp"])
def test_fused_vs_ref_k_sweep(setup, engine, k, exact):
    """The fused engines (bins path) vs the seed across k x exact, at
    schedule lengths off the main parity sweep: exact=True is bit-equal
    (the bins decomposition IS the flat merge), norm mode to tolerance."""
    data, queries, index = setup
    for steps in (2, 8):
        d_ref, i_ref = search_batch_fixed_ref(
            index, queries, k=k, r0=0.5, steps=steps, engine="jnp"
        )
        d_new, i_new = search_batch_fixed(
            index, queries, k=k, r0=0.5, steps=steps, engine=engine,
            interpret=True, exact=exact,
        )
        if exact:
            np.testing.assert_array_equal(np.asarray(d_new), np.asarray(d_ref))
        else:
            np.testing.assert_allclose(
                np.asarray(d_new), np.asarray(d_ref), rtol=1e-2, atol=1e-2
            )
        assert _idsets_equal(d_ref, i_ref, d_new, i_new)


@pytest.fixture(scope="module")
def setup_quant(setup):
    """Quantized twins of the fixture index (same data, same LSH key)."""
    data, queries, _ = setup
    out = {}
    for dt in ("bf16", "int8"):
        params = DBLSHParams.derive(
            n=2048, d=24, c=1.5, t=48, k=10, K=8, L=3,
            inline_vectors=True, max_blocks=32, quant_dtype=dt,
        )
        out[dt] = build(jax.random.split(jax.random.key(29))[1],
                        jnp.asarray(data), params)
    return data, queries, out


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
@pytest.mark.parametrize("engine", ENGINES)
def test_quant_recall_band(setup_quant, engine, dtype):
    """Documented tolerance band for the quantized distance path: id-set
    recall vs the fp32 search on the same index >= 0.95 (NOT
    bit-equality — the shortlist is approximate; only a true neighbor
    falling off its bin's 4k shortlist can be lost).  Returned distances
    are exact fp32 (the re-rank), so every returned (id, dist) pair is
    itself exact."""
    data, queries, indexes = setup_quant
    index = indexes[dtype]
    d_fp, i_fp = search_batch_fixed(
        index, queries, k=K_TEST, r0=0.5, steps=8, engine=engine,
        interpret=True,
    )
    d_q, i_q = search_batch_fixed(
        index, queries, k=K_TEST, r0=0.5, steps=8, engine=engine,
        interpret=True, dtype=dtype,
    )
    i_fp, i_q = np.asarray(i_fp), np.asarray(i_q)
    d_fp_n, d_q_n = np.asarray(d_fp), np.asarray(d_q)
    rec = np.mean([
        len(set(i_q[r]) & set(i_fp[r])) / K_TEST for r in range(i_fp.shape[0])
    ])
    assert rec >= 0.95, rec
    # the re-rank contract: every returned distance is the fp32 distance
    # of its id (norm-form re-rank vs this diff-form oracle: rounding
    # only, no quantization error survives the re-rank)
    for r in range(i_q.shape[0]):
        finite = np.isfinite(d_q_n[r])
        ids = i_q[r][finite]
        true = np.sqrt(np.sum(
            (data[ids] - np.asarray(queries)[r][None, :]) ** 2, axis=-1))
        np.testing.assert_allclose(d_q_n[r][finite], true, rtol=1e-3,
                                   atol=1e-3)


def test_quant_termination_stats_match_fp32(setup_quant):
    """C1/C2 accounting runs on fp32 admission counts and exact re-ranked
    distances, so the termination stats of a quantized search match the
    fp32 search on the same index."""
    from repro.core import Termination
    data, queries, indexes = setup_quant
    index = indexes["int8"]
    term = Termination(use_c1=True, use_c2=True)
    *_, s_fp, e_fp = search_batch_fixed(
        index, queries, k=K_TEST, r0=0.5, steps=8, with_explain=True,
        termination=term,
    )
    *_, s_q, e_q = search_batch_fixed(
        index, queries, k=K_TEST, r0=0.5, steps=8, with_explain=True,
        termination=term, dtype="int8",
    )
    np.testing.assert_array_equal(
        np.asarray(s_fp["radius_steps"]), np.asarray(s_q["radius_steps"])
    )
    np.testing.assert_array_equal(
        np.asarray(e_fp["term_cause"]), np.asarray(e_q["term_cause"])
    )


def test_dtype_validation(setup, setup_quant):
    """dtype errors are loud: unknown names, quant+exact (the quantized
    path is a shortlist, not bit-exact), and index/dtype mismatches."""
    data, queries, index = setup
    _, _, indexes = setup_quant
    with pytest.raises(ValueError, match="dtype"):
        search_batch_fixed(index, queries, k=5, dtype="fp64")
    with pytest.raises(ValueError, match="exact"):
        search_batch_fixed(indexes["int8"], queries, k=5, dtype="int8",
                           exact=True)
    with pytest.raises(ValueError, match="quant_dtype"):
        search_batch_fixed(index, queries, k=5, dtype="int8")
    with pytest.raises(ValueError, match="quant_dtype"):
        search_batch_fixed(indexes["bf16"], queries, k=5, dtype="int8")


def test_norm_blocks_invariant(setup):
    """norm_blocks is slot-aligned with ids_blocks: finite slots hold the
    squared norm of their point, padded slots +inf."""
    data, queries, index = setup
    norms = np.sum(np.asarray(data) ** 2, axis=-1)
    nb_arr = np.asarray(index.norm_blocks)
    ids = np.asarray(index.ids_blocks)
    valid = ids < index.n
    np.testing.assert_allclose(
        nb_arr[valid], norms[ids[valid]], rtol=1e-6
    )
    assert np.isinf(nb_arr[~valid]).all()
