"""Competitor/baseline methods the paper compares against (§VI-A).

* ``brute_force``   — exact k-NN oracle (ground truth for recall/ratio).
* ``FBLSH``         — the paper's own ablation: identical (K,L)-index but
                      *fixed* (query-oblivious) bucketing. Isolates the
                      value of query-centric dynamic buckets.
* ``MQIndex``       — dynamic metric-query scheme (PM-LSH/SRS family):
                      one m-dim projected space, candidates = beta*n
                      nearest in the projected space, verified exactly.
* ``C2Index``       — collision-counting scheme (QALSH family): m one-dim
                      projections, candidates = points colliding on >= l
                      projections at query-centric width w.

These are compact but faithful reimplementations of the *schemes* (the
candidate-generation rules and cost profiles), which is what the paper's
comparison exercises.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from . import hashing

__all__ = ["brute_force", "FBLSH", "MQIndex", "C2Index"]

_INF = jnp.inf


@partial(jax.jit, static_argnames=("k",))
def brute_force(data: jax.Array, Q: jax.Array, k: int = 50):
    """Exact k-NN via a blocked distance matrix. Returns (dists, ids)."""
    # ||q - x||^2 = ||q||^2 - 2 q.x + ||x||^2  (MXU-friendly).  HIGHEST:
    # the oracle must be exact on a TPU too, where a DEFAULT f32 matmul
    # is a single bf16 pass
    qn = jnp.sum(jnp.square(Q), axis=-1, keepdims=True)  # (Qn,1)
    xn = jnp.sum(jnp.square(data), axis=-1)  # (n,)
    dots = jnp.matmul(Q, data.T, precision=jax.lax.Precision.HIGHEST)
    d2 = qn - 2.0 * dots + xn  # (Qn, n)
    d2 = jnp.maximum(d2, 0.0)
    neg, ids = jax.lax.top_k(-d2, k)
    return jnp.sqrt(-neg), ids


# ---------------------------------------------------------------------------
# FB-LSH: static (K, L)-index with fixed-width buckets.
# ---------------------------------------------------------------------------


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["proj_vecs", "proj", "offsets", "data"],
    meta_fields=["K", "L", "w0", "c", "t", "max_radius_steps", "cand_cap"],
)
@dataclasses.dataclass
class FBLSH:
    """Fixed-bucketing LSH over the same (K, L) projections.

    Bucket code of point o in table i: floor((h_ij(o) + b_ij) / w). The
    query probes its *own* bucket only — reproducing the hash-boundary
    issue DB-LSH eliminates. The radius schedule is emulated by virtual
    rehashing (recomputing codes at width w0*r), as in LSB/E2LSH's
    r in {1, c, c^2, ...} suite-of-indexes semantics.
    """

    proj_vecs: jax.Array  # (L, K, d)
    proj: jax.Array  # (L, n, K)
    offsets: jax.Array  # (L, K) uniform [0, w0)
    data: jax.Array  # (n, d)
    K: int
    L: int
    w0: float
    c: float
    t: int
    max_radius_steps: int
    cand_cap: int

    @staticmethod
    def build(key, data, K, L, w0, c, t=100, max_radius_steps=24, cand_cap=0):
        kp, kb = jax.random.split(key)
        proj_vecs = hashing.sample_projections(kp, data.shape[1], K, L)
        proj = hashing.project(data, proj_vecs)
        offsets = jax.random.uniform(kb, (L, K), minval=0.0, maxval=w0)
        cand_cap = cand_cap or (2 * t + 64)
        return FBLSH(proj_vecs, proj, offsets, data, K, L, w0, c, t,
                     max_radius_steps, cand_cap)

    def _probe(self, gq, w):
        """Candidates colliding with q's bucket in >= 1 table at width w."""
        codes = jnp.floor((self.proj + self.offsets[:, None, :]) / w)  # (L,n,K)
        qcodes = jnp.floor((gq + self.offsets) / w)  # (L,K)
        hit = jnp.all(codes == qcodes[:, None, :], axis=-1)  # (L,n)
        return jnp.any(hit, axis=0)  # (n,)

    def search(self, q, k=50, r0=1.0):
        n = self.data.shape[0]
        gq = jnp.einsum("lkd,d->lk", self.proj_vecs, q)
        cap = self.cand_cap

        def body(state):
            j, r, bd, bi, done = state
            hit = self._probe(gq, self.w0 * r)
            # fixed-capacity candidate selection (budget 2tL+k analogue)
            cand = jnp.sort(jnp.where(hit, jnp.arange(n), n))[: cap * self.L]
            xb = jnp.take(self.data, cand, axis=0, mode="fill", fill_value=0.0)
            d2 = jnp.sum(jnp.square(xb - q), axis=-1)
            d2 = jnp.where(cand < n, d2, _INF)
            alld = jnp.concatenate([bd, d2])
            alli = jnp.concatenate([bi, cand.astype(jnp.int32)])
            order = jnp.lexsort((alld, alli))
            ids_s, d_s = jnp.take(alli, order), jnp.take(alld, order)
            first = jnp.concatenate([jnp.ones((1,), bool), ids_s[1:] != ids_s[:-1]])
            d_s = jnp.where(first & (ids_s < n), d_s, _INF)
            neg, ti = jax.lax.top_k(-d_s, k)
            bd, bi = -neg, jnp.take(ids_s, ti)
            nver = jnp.sum(first & (ids_s < n) & jnp.isfinite(d_s))
            done = (bd[k - 1] <= jnp.square(self.c * r)) | (
                nver >= 2 * self.t * self.L + k
            )
            return j + 1, r * self.c, bd, bi, done

        state = (
            jnp.asarray(0),
            jnp.asarray(r0, jnp.float32),
            jnp.full((k,), _INF),
            jnp.full((k,), n, jnp.int32),
            jnp.asarray(False),
        )
        state = jax.lax.while_loop(
            lambda s: (~s[4]) & (s[0] < self.max_radius_steps), body, state
        )
        return jnp.sqrt(state[2]), state[3]

    def search_batch(self, Q, k=50, r0=1.0):
        return jax.jit(
            jax.vmap(lambda q: self.search(q, k=k, r0=r0)), static_argnums=()
        )(Q)


# ---------------------------------------------------------------------------
# MQ (PM-LSH / SRS family): metric queries in one projected space.
# ---------------------------------------------------------------------------


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["proj_vecs", "proj", "data"],
    meta_fields=["m", "beta"],
)
@dataclasses.dataclass
class MQIndex:
    proj_vecs: jax.Array  # (m, d)
    proj: jax.Array  # (n, m)
    data: jax.Array
    m: int
    beta: float

    @staticmethod
    def build(key, data, m=15, beta=0.08):
        pv = jax.random.normal(key, (m, data.shape[1]), jnp.float32)
        return MQIndex(pv, data @ pv.T, data, m, beta)

    @partial(jax.jit, static_argnames=("k",))
    def search_batch(self, Q, k=50):
        n = self.data.shape[0]
        ncand = max(k, int(self.beta * n))
        gq = Q @ self.proj_vecs.T  # (Qn, m)
        # exact NN in the projected space (the 'metric query')
        d2p = (
            jnp.sum(jnp.square(gq), -1, keepdims=True)
            - 2.0 * gq @ self.proj.T
            + jnp.sum(jnp.square(self.proj), -1)
        )
        _, cand = jax.lax.top_k(-d2p, ncand)  # (Qn, ncand)
        xb = jnp.take(self.data, cand, axis=0)  # (Qn, ncand, d)
        d2 = jnp.sum(jnp.square(xb - Q[:, None, :]), axis=-1)
        neg, ti = jax.lax.top_k(-d2, k)
        return jnp.sqrt(jnp.maximum(-neg, 0.0)), jnp.take_along_axis(cand, ti, 1)


# ---------------------------------------------------------------------------
# C2 (QALSH family): collision counting over one-dim projections.
# ---------------------------------------------------------------------------


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["proj_vecs", "proj", "data"],
    meta_fields=["m", "l", "w", "cand_cap"],
)
@dataclasses.dataclass
class C2Index:
    proj_vecs: jax.Array  # (m, d)
    proj: jax.Array  # (n, m)
    data: jax.Array
    m: int
    l: int
    w: float
    cand_cap: int

    @staticmethod
    def build(key, data, m=60, collision_ratio=0.45, w=2.0, cand_cap=0):
        pv = jax.random.normal(key, (m, data.shape[1]), jnp.float32)
        l = max(1, int(collision_ratio * m))
        cand_cap = cand_cap or max(256, data.shape[0] // 20)
        return C2Index(pv, data @ pv.T, data, m, l, w, cand_cap)

    @partial(jax.jit, static_argnames=("k",))
    def search_batch(self, Q, k=50):
        n = self.data.shape[0]
        gq = Q @ self.proj_vecs.T  # (Qn, m)
        # query-centric one-dim buckets, count collisions per point
        coll = jnp.abs(self.proj[None, :, :] - gq[:, None, :]) <= 0.5 * self.w
        counts = jnp.sum(coll, axis=-1)  # (Qn, n)
        hit = counts >= self.l
        idx = jnp.argsort(~hit, axis=-1, stable=True)[:, : self.cand_cap]
        valid = jnp.take_along_axis(hit, idx, axis=1)
        xb = jnp.take(self.data, idx, axis=0)
        d2 = jnp.sum(jnp.square(xb - Q[:, None, :]), axis=-1)
        d2 = jnp.where(valid, d2, _INF)
        neg, ti = jax.lax.top_k(-d2, k)
        ids = jnp.take_along_axis(idx, ti, 1)
        ids = jnp.where(jnp.isfinite(-neg), ids, n)
        return jnp.sqrt(jnp.maximum(-neg, 0.0)), ids
