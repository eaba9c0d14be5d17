"""Incremental DB-LSH index maintenance: insert / delete / compact.

The paper builds a static index; a production vector store needs online
updates. The dense STR-block structure supports them naturally:

* **insert** — project the new points with the *existing* LSH functions
  (Observation 1 keeps every guarantee intact: the hash family is fixed,
  only the point set grows), STR-order them locally, and append whole
  blocks per table. Query cost is unchanged (MBR mask covers old + new
  blocks); block quality of the appended region equals a fresh build of
  that region. K/L were sized for the build-time n — rebuild (compact)
  when n grows past ~2x, as K ~ log n.

* **delete** — tombstone the slots holding the deleted ids (+inf
  projection, sentinel id) and re-tighten the affected block MBRs.
  Deleted points can never be returned (the in-box test fails and the
  id is invalid); space is reclaimed at the next compact.

* **compact** — rebuild from the surviving points with a fresh key
  (also re-derives K/L for the current n, unless the caller fixed them).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from . import hashing
from .index import DBLSHIndex, _str_order, build, quantize_blocks
from .params import DBLSHParams

__all__ = ["grown_params", "insert", "delete", "compact", "live_count",
           "live_ids_padded"]

_INF = jnp.inf


def grown_params(p: DBLSHParams, n_total: int) -> DBLSHParams:
    """Params for an index grown in place to ``n_total`` points.

    ``max_blocks`` may have been capped by the *build-time* block count
    (:meth:`DBLSHParams.resolve` takes ``min(budget, ceil(n/B))``);
    appended blocks lift that cap, so it is re-derived at the new n —
    otherwise a small index could never probe past its original blocks
    and inserted points would be unreachable.  An explicitly larger
    setting is kept."""
    grown = dataclasses.replace(p, n=n_total, max_blocks=0).resolve().max_blocks
    return dataclasses.replace(p, n=n_total, max_blocks=max(p.max_blocks, grown))


def insert(index: DBLSHIndex, new_points: jax.Array) -> DBLSHIndex:
    """Append ``new_points`` (m, d) as new STR blocks per table."""
    p = index.params
    m, d = new_points.shape
    assert d == p.d, (d, p.d)
    n_old = index.n
    B = p.block_size
    nb_new = -(-m // B)
    m_pad = nb_new * B
    n_total = n_old + m

    proj = hashing.project(new_points, index.proj_vecs)  # (L, m, K)
    orders = jax.vmap(lambda pr: _str_order(pr, B))(proj)  # (L, m)

    new_norms = jnp.sum(jnp.square(new_points), axis=-1)  # (m,)

    def _pack(order, proj_t):
        ps = jnp.take(proj_t, order, axis=0)
        ps = jnp.concatenate(
            [ps, jnp.full((m_pad - m, p.K), _INF, ps.dtype)]
        ).reshape(nb_new, B, p.K)
        ids = jnp.concatenate(
            [order.astype(jnp.int32) + n_old,
             jnp.full((m_pad - m,), n_total, jnp.int32)]
        ).reshape(nb_new, B)
        nrm = jnp.concatenate(
            [jnp.take(new_norms, order), jnp.full((m_pad - m,), _INF)]
        ).reshape(nb_new, B).astype(jnp.float32)
        finite = jnp.isfinite(ps[..., :1])
        lo = jnp.min(ps, axis=1)
        hi = jnp.max(jnp.where(finite, ps, -_INF), axis=1)
        return ps, ids, nrm, lo, hi

    pb, ib, nrm_b, lo, hi = jax.vmap(_pack)(orders, proj)

    # old sentinel ids (== n_old) must move to the new sentinel n_total
    old_ids = jnp.where(index.ids_blocks >= n_old, n_total, index.ids_blocks)

    new_params = grown_params(p, n_total)
    fields = dict(
        proj_vecs=index.proj_vecs,
        proj_blocks=jnp.concatenate([index.proj_blocks, pb], axis=1),
        ids_blocks=jnp.concatenate([old_ids, ib], axis=1),
        mbr_lo=jnp.concatenate([index.mbr_lo, lo], axis=1),
        mbr_hi=jnp.concatenate([index.mbr_hi, hi], axis=1),
        data=jnp.concatenate([index.data, new_points], axis=0),
        # old padded / tombstoned slots are already +inf (fill covers
        # everything >= n_old), so a plain concat stays slot-aligned
        norm_blocks=jnp.concatenate([index.norm_blocks, nrm_b], axis=1),
        params=new_params,
    )
    if p.inline_vectors:
        def _pack_vecs(order):
            v = jnp.take(new_points, order, axis=0)
            v = jnp.concatenate([v, jnp.zeros((m_pad - m, d), v.dtype)])
            return v.reshape(nb_new, B, d)

        vb = jax.vmap(_pack_vecs)(orders)
        fields["vec_blocks"] = jnp.concatenate([index.vec_blocks, vb], axis=1)
    else:
        fields["vec_blocks"] = index.vec_blocks
    if p.quant_dtype != "none":
        # quantization is per-slot, so the appended region quantizes
        # independently of the old blocks (ids local to new_points;
        # padded slots hit the zero fill — never admitted anyway)
        qb, qs = quantize_blocks(new_points, ib - n_old, p.quant_dtype)
        fields["qvec_blocks"] = jnp.concatenate([index.qvec_blocks, qb], axis=1)
        fields["qvec_scale"] = jnp.concatenate([index.qvec_scale, qs], axis=1)
    else:
        fields["qvec_blocks"] = index.qvec_blocks
        fields["qvec_scale"] = index.qvec_scale
    return DBLSHIndex(**fields)


def delete(index: DBLSHIndex, del_ids: jax.Array) -> DBLSHIndex:
    """Tombstone ``del_ids`` (k,); re-tighten affected MBRs.

    Ids are int32 end to end (inputs are cast, matching search results
    and compaction id maps).  Values outside ``[0, n)`` are no-ops: the
    sentinel ``n`` only re-tombstones already-dead slots and anything
    else matches nothing — the sharded wrappers rely on this for
    SPMD-uniform deletes and for gids landing in stride headroom."""
    p = index.params
    n = index.n
    del_ids = jnp.asarray(del_ids, jnp.int32)
    dead = jnp.isin(index.ids_blocks, del_ids)  # (L, nb, B)
    ids = jnp.where(dead, n, index.ids_blocks)
    proj = jnp.where(dead[..., None], _INF, index.proj_blocks)
    finite = jnp.isfinite(proj[..., :1])
    lo = jnp.min(proj, axis=2)
    hi = jnp.max(jnp.where(finite, proj, -_INF), axis=2)
    return DBLSHIndex(
        proj_vecs=index.proj_vecs,
        proj_blocks=proj,
        ids_blocks=ids,
        mbr_lo=lo,
        mbr_hi=hi,
        data=index.data,
        vec_blocks=index.vec_blocks,
        norm_blocks=jnp.where(dead, _INF, index.norm_blocks),
        # quantized blocks stay as-is: tombstoned slots project to +inf,
        # so hw=inf keeps them out of every schedule bin, and the exact
        # re-rank masks their sentinel ids — no touch-up needed
        qvec_blocks=index.qvec_blocks,
        qvec_scale=index.qvec_scale,
        params=index.params,
    )


def live_count(index: DBLSHIndex) -> int:
    """Number of live (non-tombstoned) points, from table 0."""
    return int(jnp.sum(index.ids_blocks[0] < index.n))


def live_ids_padded(index: DBLSHIndex) -> jax.Array:
    """Sorted live point ids, padded with the sentinel ``n`` to the
    static length ``n + 1`` — the jit-stable form of the live scan
    (compaction's gather order), usable inside ``shard_map``."""
    n = index.n
    return jnp.sort(
        jnp.unique(
            jnp.where(index.ids_blocks[0] < n, index.ids_blocks[0], n),
            size=n + 1, fill_value=n,
        )
    )


def compact(index: DBLSHIndex, key) -> tuple[DBLSHIndex, jax.Array]:
    """Rebuild from surviving points (re-derives a derived K/L for the
    live n; a fixed one stays — :meth:`DBLSHParams.rebuilt`).

    Returns (new_index, id_map) where id_map (n_old,) holds each old
    id's new id, or -1 if deleted."""
    p = index.params
    n_old = index.n
    live_ids = live_ids_padded(index)
    live_ids = live_ids[live_ids < n_old]
    n_live = int(live_ids.shape[0])
    data = jnp.take(index.data, live_ids, axis=0)
    new_params = p.rebuilt(n_live)
    id_map = jnp.full((n_old,), -1, jnp.int32)
    id_map = id_map.at[live_ids].set(jnp.arange(n_live, dtype=jnp.int32))
    return build(key, data, new_params), id_map
