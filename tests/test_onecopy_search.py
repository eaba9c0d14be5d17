"""The one-copy layout (``inline_vectors=False``) on the served path.

* **served kernel engine vs the seed** — a one-copy collection at the
  widths of the 10M deployment (K = 10, L = 5, B = 64, M = 20), served
  through ``StoreService(engine="kernel")`` in the Pallas interpreter,
  returns the multi-pass ``search_batch_fixed_ref(engine="jnp")``'s ids
  in order, its schedule steps exactly and its distances to norm-form
  tolerance; its candidate counts are the one-pass ``jnp`` engine's.
* **slot gathers** — ``_slot_gather`` reads the selected blocks' slots
  out of the stored (L, nb, ...) arrays with the values the old
  whole-array reshape + ``take`` gave, fills included, bit for bit; and
  swapping it for that formulation changes no engine's results on
  either placement.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.core import DBLSHParams, build, search_batch_fixed, search_batch_fixed_ref
from repro.core import serve_search
from repro.core.distributed import build_sharded, search_sharded
from repro.data import make_clustered, normalize_scale
from repro.store import Collection, StoreService

N, D, K, L, B, M, k = 4096, 32, 10, 5, 64, 20, 10
STEPS, R0 = 8, 1.0


@pytest.fixture(scope="module")
def setup():
    kd, kb = jax.random.split(jax.random.key(41))
    allpts = make_clustered(kd, N + 64, D, n_clusters=16, spread=0.02)
    data, queries = allpts[:N], allpts[N:]
    data, queries, _ = normalize_scale(data, queries)
    return np.asarray(data), np.asarray(queries), kb


def _params(inline: bool) -> DBLSHParams:
    return DBLSHParams.derive(n=N, d=D, c=1.5, t=64, k=k, K=K, L=L,
                              max_blocks=M, inline_vectors=inline)


@pytest.fixture(scope="module")
def onecopy(setup):
    data, _, kb = setup
    return Collection.create("onecopy", kb, data, params=_params(False))


def test_served_kernel_onecopy_matches_ref(setup, onecopy):
    _, queries, _ = setup
    assert not onecopy.index.params.inline_vectors
    svc = StoreService(batch_shapes=(8, 32), default_k=k, r0=R0, steps=STEPS,
                       engine="kernel", interpret=True, max_wait_ms=1e9,
                       cache_size=0)
    svc.attach(onecopy)
    tickets = [svc.submit("onecopy", q) for q in queries]
    svc.flush()
    assert all(t.done and t.error is None for t in tickets)
    d_srv = np.stack([t.dists for t in tickets])
    i_srv = np.stack([t.ids for t in tickets])

    Q = jnp.asarray(queries)
    d_ref, i_ref, s_ref = search_batch_fixed_ref(
        onecopy.index, Q, k=k, r0=R0, steps=STEPS, engine="jnp", with_stats=True)
    np.testing.assert_array_equal(i_srv, np.asarray(i_ref))
    np.testing.assert_allclose(d_srv, np.asarray(d_ref), rtol=1e-2, atol=1e-2)
    np.testing.assert_array_equal([t.radius_steps for t in tickets],
                                  np.asarray(s_ref["radius_steps"]))
    *_, s_jnp = search_batch_fixed(onecopy.index, Q, k=k, r0=R0, steps=STEPS,
                                   engine="jnp", with_stats=True)
    np.testing.assert_array_equal([t.candidates for t in tickets],
                                  np.asarray(s_jnp["candidates"]))
    # M = 20 of 64 blocks a table: the cap binds, as at 10M
    assert max(t.candidates for t in tickets) <= L * M * B


def _flat_take(a, blk_q, M, fill):
    """The old formulation: (L, nb) merged into one axis, then ``take``."""
    L, nb = a.shape[:2]
    Qn = blk_q.shape[0]
    x = jnp.take(a.reshape((L * nb,) + a.shape[2:]), blk_q, axis=0,
                 mode="fill", fill_value=fill)
    return x.reshape((Qn, L, M) + a.shape[2:])


@pytest.mark.parametrize("field,fill", [("proj_blocks", jnp.inf),
                                        ("ids_blocks", N),
                                        ("norm_blocks", jnp.inf)])
def test_slot_gather_matches_flat_take(onecopy, field, fill):
    index = onecopy.index
    nb = index.nb
    rng = np.random.default_rng(7)
    # slot (l, m) holds a block of table l, as select writes them
    local = rng.integers(0, nb, size=(6, L, M))
    blk_q = (local + nb * np.arange(L)[:, None]).reshape(6, L * M).astype(np.int32)
    blk_q[:, ::7] = L * nb  # invalid slots
    blk_q = jnp.asarray(blk_q)
    a = getattr(index, field)
    got = serve_search._slot_gather(a, blk_q, M, fill)
    want = _flat_take(a, blk_q, M, fill)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _search(placement, engine, data, queries, kb):
    if placement == "local":
        index = build(kb, jnp.asarray(data), _params(engine == "inline"))
        return search_batch_fixed(index, jnp.asarray(queries), k=k, r0=R0,
                                  steps=STEPS, engine=engine, interpret=True,
                                  with_stats=True)
    mesh = make_mesh((1,), ("data",))
    sh = build_sharded(kb, jnp.asarray(data), _params(False), mesh)
    return search_sharded(sh, jnp.asarray(queries), k=k, r0=R0, steps=STEPS,
                          mesh=mesh, with_stats=True)


@pytest.mark.parametrize("placement,engine", [("local", "jnp"), ("local", "inline"),
                                              ("local", "kernel"), ("sharded", "jnp")])
def test_engines_unchanged_by_slot_gather(setup, monkeypatch, placement, engine):
    data, queries, kb = setup
    jax.clear_caches()
    d_new, i_new, s_new = _search(placement, engine, data, queries, kb)
    monkeypatch.setattr(serve_search, "_slot_gather", _flat_take)
    jax.clear_caches()
    d_old, i_old, s_old = _search(placement, engine, data, queries, kb)
    jax.clear_caches()
    np.testing.assert_array_equal(np.asarray(i_new), np.asarray(i_old))
    np.testing.assert_array_equal(np.asarray(d_new), np.asarray(d_old))
    for name in ("radius_steps", "candidates"):
        np.testing.assert_array_equal(np.asarray(s_new[name]), np.asarray(s_old[name]))
