"""Distributed DB-LSH: dataset sharded over the mesh 'data' axis.

Every device builds a *local* DB-LSH index over its n/P slice using the
SAME LSH functions (one PRNG key → identical projection vectors — the
union of per-shard query-centric windows then equals the global window,
so Lemma 1/2 guarantees are unchanged). A query is replicated; each
shard answers a local (c,k)-ANN with the fixed-schedule engine; results
merge with one k-sized all_gather + local top-k (ids are globally
offset, hence disjoint across shards — no dedup needed at the merge).

Collective cost per query batch: one all_gather of (P, Q, k) pairs over
'data' — independent of n. This is the datastore behind
serve/retrieval.py at fleet scale.

The index is mutable in place at fleet scale too: ``insert_sharded`` /
``delete_sharded`` / ``compact_sharded`` are shard_map wrappers over
``core.updates`` (least-loaded insert routing, arithmetic global-id
translation, rebalancing per-shard rebuild with a gathered global id
remap — see the maintenance section below and DESIGN.md §9).

Global ids are **strided**: each shard owns the id segment
``[rank * stride, rank * stride + n_local)`` with ``stride >= n_local``,
so ``gid = rank * stride + local``.  Inserts grow ``n_local`` *within*
the stride and therefore never move an existing id; only
:func:`compact_sharded` (which already returns an id map) renumbers,
when it re-strides for the new per-shard count.  ``stride == n_local``
(the :func:`build_sharded` default) degenerates to dense ids that equal
global data-row indices.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..compat import shard_map as _shard_map

from . import updates as _updates
from .index import DBLSHIndex, build
from .params import DBLSHParams
from .serve_search import search_batch_fixed

__all__ = [
    "ShardedDBLSH",
    "id_stride",
    "build_sharded",
    "search_sharded",
    "shard_live_counts",
    "insert_sharded",
    "delete_sharded",
    "compact_sharded",
]

_INF = jnp.inf


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["index"],
    meta_fields=["axis", "n_total", "n_local", "stride"],
)
@dataclasses.dataclass
class ShardedDBLSH:
    index: DBLSHIndex  # arrays sharded over `axis` (see _index_specs)
    axis: str
    n_total: int
    n_local: int
    stride: int  # id segment width per shard: gid = rank * stride + local

    @property
    def id_space(self) -> int:
        """Exclusive upper bound of the global id space (and the merge
        sentinel for unfilled result slots): ``P * stride``."""
        return (self.n_total // self.n_local) * self.stride


def id_stride(n_local: int, headroom: float = 2.0, reserve: int = 0) -> int:
    """Pick a per-shard id stride with insert headroom.

    ``headroom`` scales the stride past the current per-shard count so
    ids stay stable across inserts until ``n_local`` reaches the stride;
    ``reserve`` additionally guarantees room for a known incoming batch.
    Always at least ``n_local + 1`` so one insert fits."""
    n_local = max(int(n_local), 1)
    return max(
        int(math.ceil(headroom * n_local)),
        n_local + 1,
        n_local + int(reserve),
    )


def _index_specs(axis: str, params) -> DBLSHIndex:
    """PartitionSpecs for each DBLSHIndex field (block dim sharded)."""
    return DBLSHIndex(
        proj_vecs=P(),          # same hash functions everywhere
        proj_blocks=P(None, axis),
        ids_blocks=P(None, axis),
        mbr_lo=P(None, axis),
        mbr_hi=P(None, axis),
        data=P(axis),
        vec_blocks=P(None, axis) if params.inline_vectors else P(),
        norm_blocks=P(None, axis),
        qvec_blocks=P(None, axis) if params.quant_dtype != "none" else P(),
        qvec_scale=P(None, axis) if params.quant_dtype != "none" else P(),
        params=params,
    )


def build_sharded(key, data, params_local: DBLSHParams, mesh,
                  axis: str = "data", *, stride: int | None = None
                  ) -> ShardedDBLSH:
    """data: (n, d) global (sharded or shardable over `axis`).

    ``stride`` sets the per-shard id segment width (default ``n_local``:
    dense ids that double as global data-row indices).  Pass
    :func:`id_stride` headroom when the index will take inserts and ids
    must survive them."""
    n, d = data.shape
    pn = mesh.shape[axis]
    assert n % pn == 0, (n, pn)
    n_local = n // pn
    stride = n_local if stride is None else int(stride)
    assert stride >= n_local, (stride, n_local)
    params_local = dataclasses.replace(params_local, n=n_local, d=d).resolve()

    def local_build(data_l):
        return build(key, data_l, params_local)

    specs = _index_specs(axis, params_local)
    idx = jax.jit(
        _shard_map(
            local_build, mesh=mesh, in_specs=P(axis), out_specs=specs,
        )
    )(data)
    return ShardedDBLSH(index=idx, axis=axis, n_total=n, n_local=n_local,
                        stride=stride)


@partial(jax.jit, static_argnames=("k", "steps", "mesh", "with_stats",
                                   "exact", "termination", "with_explain",
                                   "dtype"))
def search_sharded(s: ShardedDBLSH, Q: jax.Array, k: int = 0, r0: float = 1.0,
                   steps: int = 8, mesh=None, with_stats: bool = False,
                   exact: bool = False, termination=None,
                   with_explain: bool = False, dtype: str = "fp32"):
    """Replicated queries -> (Q, k) global distances/ids.

    Returned ids live in the strided space ``gid = rank * stride +
    local``; unfilled slots carry the sentinel ``s.id_space`` (always
    mask on the distances — +inf marks an unfilled slot).

    With ``with_stats`` the per-shard probe statistics survive the
    collective merge instead of being dropped at the boundary: a third
    return aggregates them per query — ``candidates`` is the psum over
    shards (total distinct slots fetched fleet-wide on the query's
    behalf) and ``radius_steps`` the pmax (the schedule runs lockstep,
    so the slowest shard's step count is the query's wall-clock probe
    depth).

    ``termination`` (a :class:`~repro.core.serve_search.Termination`)
    applies *per shard*: each device evaluates the C1/C2 done masks over
    its local candidates and exits its own while_loop independently (no
    collectives inside the loop).  This is sound and conservative — a
    shard's local k-th distance upper-bounds the global k-th, so local
    C2 never fires before the global condition would, and local C1 sees
    only the shard's own verified slots.

    ``with_explain`` (implies ``with_stats``) additionally returns the
    per-shard EXPLAIN arrays *before* the pmax/psum collapse — the
    ``repro.obs.explain`` attribution feed.  One extra all_gather of the
    small per-query counters (no candidate data moves):

    * ``shard_steps`` (P, Qn), ``shard_slots`` (P, Qn),
      ``shard_cause`` (P, Qn) — each shard's schedule depth, verified
      slots, and terminate cause for every query;
    * ``step_slots`` (Qn, steps) — fleet-wide admitted-delta slots per
      step (psum over shards; rows sum to ``stats['candidates']``);
    * ``step_half`` (steps,), ``term_cause`` / ``final_radius`` (Qn,) —
      the critical path's view: the cause/radius on the shard that ran
      deepest (which set the pmax'd ``radius_steps``)."""
    p = s.index.params
    k = k or p.k
    axis = s.axis
    n_local, stride = s.n_local, s.stride
    space = s.id_space  # merge sentinel: one past the last valid gid
    if with_explain:
        with_stats = True

    def local_search(idx_tree, Qr):
        out = search_batch_fixed(
            idx_tree, Qr, k=k, r0=r0, steps=steps, with_stats=with_stats,
            exact=exact, termination=termination, with_explain=with_explain,
            dtype=dtype,
        )
        d, i = out[0], out[1]
        rank = jax.lax.axis_index(axis)
        gi = jnp.where(i < n_local, i + rank * stride, space)
        d_all = jax.lax.all_gather(d, axis)  # (P, Qn, k)
        i_all = jax.lax.all_gather(gi, axis)
        Qn = Qr.shape[0]
        d_flat = jnp.moveaxis(d_all, 0, 1).reshape(Qn, -1)
        i_flat = jnp.moveaxis(i_all, 0, 1).reshape(Qn, -1)
        d2 = jnp.where(jnp.isfinite(d_flat), d_flat, _INF)
        neg, pos = jax.lax.top_k(-d2, k)
        ids = jnp.take_along_axis(i_flat, pos, axis=1)
        merged = (-neg, jnp.where(jnp.isfinite(-neg), ids, space))
        if with_stats:
            stats = {
                "radius_steps": jax.lax.pmax(out[2]["radius_steps"], axis),
                "candidates": jax.lax.psum(out[2]["candidates"], axis),
            }
            merged = merged + (stats,)
        if with_explain:
            lex = out[3]
            shard_steps = jax.lax.all_gather(out[2]["radius_steps"], axis)
            shard_slots = jax.lax.all_gather(out[2]["candidates"], axis)
            shard_cause = jax.lax.all_gather(lex["term_cause"], axis)
            shard_radius = jax.lax.all_gather(lex["final_radius"], axis)
            # critical path = the shard whose schedule ran deepest (ties
            # break to the lowest rank, matching pmax's value)
            crit = jnp.argmax(shard_steps, axis=0)  # (Qn,)
            take = lambda a: jnp.take_along_axis(a, crit[None], axis=0)[0]
            explain = {
                "step_half": lex["step_half"],  # replicated: same schedule
                "step_slots": jax.lax.psum(lex["step_slots"], axis),
                "term_cause": take(shard_cause),
                "final_radius": take(shard_radius),
                "shard_steps": shard_steps,
                "shard_slots": shard_slots,
                "shard_cause": shard_cause,
            }
            merged = merged + (explain,)
        return merged

    specs = _index_specs(axis, p)
    out_specs = (P(), P())
    if with_stats:
        out_specs = out_specs + ({"radius_steps": P(), "candidates": P()},)
    if with_explain:
        out_specs = out_specs + ({
            "step_half": P(), "step_slots": P(), "term_cause": P(),
            "final_radius": P(), "shard_steps": P(), "shard_slots": P(),
            "shard_cause": P(),
        },)
    return _shard_map(
        local_search, mesh=mesh,
        in_specs=(specs, P()), out_specs=out_specs,
    )(s.index, Q)


# --------------------------------------------------------------------------
# Sharded index maintenance: shard_map wrappers over ``core.updates``.
#
# SPMD keeps every shard's array shapes identical, so a mutation that
# logically touches one shard still runs on all of them: *insert*
# replicates the new batch to every shard and immediately tombstones the
# copies on all but the routed target; *delete* translates global ids to
# (shard, local) pairs arithmetically inside the map; *compact*
# rebalances survivors across shards (one all_to_all of rows) and
# rebuilds every shard at the balanced count (padding rows are
# tombstoned in the same trace).  Global ids are strided —
# ``gid = rank * stride + local`` with ``stride >= n_local`` — which
# keeps the disjoint-id merge invariant of :func:`search_sharded` AND
# keeps every existing id fixed across inserts: ``n_local`` grows inside
# the stride, the rank offset never moves.  Only compaction renumbers
# (it re-strides for the new count) and it returns the id map; the store
# layer (``store.lifecycle``) owns communicating that remap.
# --------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("mesh",))
def shard_live_counts(s: ShardedDBLSH, mesh=None) -> jax.Array:
    """Per-shard live (non-tombstoned) point counts, shape (P,) int32 —
    the routing signal for least-loaded insert placement."""
    p = s.index.params
    axis = s.axis

    def local_count(idx):
        return jnp.sum(idx.ids_blocks[0] < p.n, dtype=jnp.int32)[None]

    return _shard_map(
        local_count, mesh=mesh,
        in_specs=(_index_specs(axis, p),), out_specs=P(axis),
    )(s.index)


@partial(jax.jit, static_argnames=("mesh",))
def insert_sharded(
    s: ShardedDBLSH, new_points: jax.Array, target, mesh=None
) -> ShardedDBLSH:
    """Append ``new_points`` (m, d) to shard ``target``.

    Every shard appends the replicated batch (uniform SPMD shapes) and
    all but the target tombstone their copy in the same trace, so only
    the target's rows are live.  The inserted points' global ids are
    ``target * stride + n_local_old + j`` and every pre-existing id is
    untouched: ``n_local`` grows *within* the stride.  Raises when the
    batch would overflow the stride — that is the one renumbering event,
    and it belongs to :func:`compact_sharded`.  ``target`` is traced
    (not static), so routing to a different shard reuses the compiled
    program.
    """
    p = s.index.params
    m = int(new_points.shape[0])
    axis = s.axis
    n_old = s.n_local
    n_new = n_old + m
    if n_new > s.stride:
        raise ValueError(
            f"insert_sharded: id stride exhausted (n_local {n_old} + {m} "
            f"inserted > stride {s.stride}); compact_sharded() renumbers "
            "into a fresh stride with headroom"
        )
    pn = mesh.shape[axis]
    new_params = _updates.grown_params(p, n_new)

    def local_insert(idx, pts, tgt):
        idx2 = _updates.insert(idx, pts)
        rank = jax.lax.axis_index(axis)
        appended = jnp.arange(m, dtype=jnp.int32) + n_old
        # the target keeps its copy live: point its delete at the
        # sentinel id (a no-op); every other shard tombstones the batch
        del_ids = jnp.where(rank == tgt, jnp.int32(n_new), appended)
        return _updates.delete(idx2, del_ids)

    idx = _shard_map(
        local_insert, mesh=mesh,
        in_specs=(_index_specs(axis, p), P(), P()),
        out_specs=_index_specs(axis, new_params),
    )(s.index, jnp.asarray(new_points, jnp.float32),
      jnp.asarray(target, jnp.int32))
    return ShardedDBLSH(index=idx, axis=axis, n_total=pn * n_new,
                        n_local=n_new, stride=s.stride)


@partial(jax.jit, static_argnames=("mesh",))
def delete_sharded(s: ShardedDBLSH, gids: jax.Array, mesh=None) -> ShardedDBLSH:
    """Tombstone global ids: each shard translates ``gids`` to its local
    id space (``local = g % stride`` iff ``g // stride == rank``, the
    sentinel otherwise) and runs :func:`core.updates.delete` locally.
    A gid pointing into a shard's stride *headroom* (``g % stride >=
    n_local``) matches nothing — deleting an unallocated id is a no-op,
    like deleting a tombstone."""
    p = s.index.params
    axis = s.axis
    n_local, stride = s.n_local, s.stride

    def local_delete(idx, g):
        rank = jax.lax.axis_index(axis)
        local = jnp.where(g // stride == rank, g % stride, n_local)
        return _updates.delete(idx, local.astype(jnp.int32))

    specs = _index_specs(axis, p)
    idx = _shard_map(
        local_delete, mesh=mesh, in_specs=(specs, P()), out_specs=specs,
    )(s.index, jnp.atleast_1d(jnp.asarray(gids, jnp.int32)))
    return ShardedDBLSH(
        index=idx, axis=axis, n_total=s.n_total, n_local=n_local,
        stride=stride,
    )


@partial(jax.jit, static_argnames=("mesh", "n_keep", "src_pad", "bucket",
                                   "stride_new", "new_params"))
def _compact_sharded_jit(s: ShardedDBLSH, key, targets, send_start, send_cnt,
                         reasm, src_off, newgid_by_ord, mesh=None, n_keep=0,
                         src_pad=0, bucket=0, stride_new=0, new_params=None):
    """Traced half of :func:`compact_sharded`.

    All routing decisions (``targets`` … ``newgid_by_ord``) are computed
    on host from the per-shard live counts and ride in as replicated
    arrays — the trace itself is just gather, one all_to_all, rebuild,
    tombstone-pad, and the id-map scatter.  Shapes (``n_keep``,
    ``src_pad``, ``bucket``) are static so repeated compacts at the same
    geometry reuse the compiled program while the routing *values* flow.
    """
    p = s.index.params
    axis = s.axis
    n_old = s.n_local
    stride_old = s.stride
    pn = mesh.shape[axis]
    d = p.d

    def local_compact(idx, targets, send_start, send_cnt, reasm, src_off,
                      newgid_by_ord):
        rank = jax.lax.axis_index(axis)
        live_sorted = _updates.live_ids_padded(idx)  # (n_old + 1,) asc
        surv = live_sorted[:src_pad]  # local survivor ids, sentinel n_old
        rows = jnp.take(idx.data, surv, axis=0, mode="fill", fill_value=0.0)
        rows = jnp.concatenate(
            [rows, jnp.zeros((1, d), rows.dtype)]
        )  # slot src_pad: the send-padding row
        # --- migration: bucket survivors by destination shard ----------
        # survivors are globally ordered by (rank, local id); the host
        # split that order into balanced contiguous destination ranges,
        # so each (src, dst) pair exchanges one contiguous run, padded
        # to the fleet-wide max run length for the collective
        t = jnp.arange(bucket, dtype=jnp.int32)
        starts = send_start[rank]  # (P,) first survivor rank per dst
        cnts = send_cnt[rank]      # (P,) run length per dst
        send_idx = jnp.where(
            t[None, :] < cnts[:, None], starts[:, None] + t[None, :], src_pad
        )
        send = jnp.take(rows, send_idx.reshape(-1), axis=0)
        send = send.reshape(pn, bucket, d)
        recv = jax.lax.all_to_all(send, axis, 0, 0)  # (P_src, bucket, d)
        recv = jnp.concatenate(
            [recv.reshape(pn * bucket, d), jnp.zeros((1, d), recv.dtype)]
        )  # slot pn * bucket: the reassembly-padding row
        data_new = jnp.take(recv, reasm[rank], axis=0)  # (n_keep, d)
        new_idx = build(key, data_new, new_params)
        slot = jnp.arange(n_keep, dtype=jnp.int32)
        # shards under the balanced max carry padding rows: tombstone
        # them (on a full shard this degenerates to the sentinel)
        pad_ids = jnp.where(slot >= targets[rank], slot, jnp.int32(n_keep))
        new_idx = _updates.delete(new_idx, pad_ids)
        # --- old gid -> new gid over this shard's old stride segment ---
        ords = src_off[rank] + jnp.arange(src_pad, dtype=jnp.int32)
        newgid = jnp.take(newgid_by_ord, ords, mode="fill", fill_value=-1)
        id_map = jnp.full((stride_old,), -1, jnp.int32)
        id_map = id_map.at[surv].set(
            jnp.where(surv < n_old, newgid, -1).astype(jnp.int32),
            mode="drop",  # padded surv entries may fall out of range
        )
        return new_idx, id_map

    return _shard_map(
        local_compact, mesh=mesh,
        in_specs=(_index_specs(axis, p), P(), P(), P(), P(), P(), P()),
        out_specs=(_index_specs(axis, new_params.resolve()), P(axis)),
    )(s.index, targets, send_start, send_cnt, reasm, src_off, newgid_by_ord)


def compact_sharded(
    s: ShardedDBLSH, key, mesh, *, headroom: float = 1.0, reserve: int = 0
) -> tuple[ShardedDBLSH, jax.Array]:
    """Rebalancing rebuild from survivors (fresh K/L for the new n).

    Survivors — ordered by ascending old global id (shard-major, then
    local) — are re-partitioned into *balanced* contiguous runs, one per
    destination shard (counts differ by at most 1), migrated with a
    single padded all_to_all, and every shard rebuilds with the *same*
    fresh key (identical hash functions across shards, the
    :func:`build_sharded` invariant).  Shards under the balanced max pad
    with tombstoned zero rows.  ``headroom`` / ``reserve`` size the new
    id stride via :func:`id_stride` (``headroom=1.0`` keeps dense ids,
    matching the :func:`build_sharded` default).

    Returns ``(new_sharded, id_map)`` with ``id_map`` (id_space_old,)
    mapping each old global id to its new global id, or -1 if deleted
    (stride-headroom holes map to -1 too).  New ids ascend with old ids,
    so a payload scattered through the map stays aligned.
    """
    p = s.index.params
    axis = s.axis
    pn = int(mesh.shape[axis])
    counts = np.asarray(shard_live_counts(s, mesh=mesh)).astype(np.int64)
    total = int(counts.sum())
    if total == 0:
        raise ValueError("compact_sharded: no live points on any shard")
    base, rem = divmod(total, pn)
    targets = (base + (np.arange(pn) < rem)).astype(np.int64)
    n_keep = int(targets.max())
    stride_new = id_stride(n_keep, headroom, reserve)
    # contiguous survivor-ordinal ranges: src shard s owns
    # [src_off[s], src_off[s+1]), dst shard r receives [dst_off[r], ...)
    src_off = np.concatenate([[0], np.cumsum(counts)])
    dst_off = np.concatenate([[0], np.cumsum(targets)])
    lo = np.maximum(src_off[:-1, None], dst_off[None, :-1])  # (P_src, P_dst)
    hi = np.minimum(src_off[1:, None], dst_off[None, 1:])
    send_cnt = np.maximum(hi - lo, 0)
    bucket = max(int(send_cnt.max()), 1)
    send_start = lo - src_off[:-1, None]  # local survivor rank of run start
    # new gid of each global survivor ordinal (the renumbering itself)
    ords = np.arange(total)
    dst = np.clip(np.searchsorted(dst_off, ords, side="right") - 1, 0, pn - 1)
    newgid_by_ord = (dst * stride_new + (ords - dst_off[dst])).astype(np.int32)
    # reassembly: dst shard r, slot j  <-  flat row of its (P, bucket) recv
    o = dst_off[:-1, None] + np.arange(n_keep)[None, :]  # (P_dst, n_keep)
    srcs = np.clip(np.searchsorted(src_off, o, side="right") - 1, 0, pn - 1)
    pos = o - lo[srcs, np.arange(pn)[:, None]]
    valid = np.arange(n_keep)[None, :] < targets[:, None]
    reasm = np.where(valid, srcs * bucket + pos, pn * bucket).astype(np.int64)
    new_params = p.rebuilt(n_keep)
    idx, id_map = _compact_sharded_jit(
        s, key,
        jnp.asarray(targets, jnp.int32),
        jnp.asarray(send_start, jnp.int32),
        jnp.asarray(send_cnt, jnp.int32),
        jnp.asarray(reasm, jnp.int32),
        jnp.asarray(src_off[:-1], jnp.int32),
        jnp.asarray(newgid_by_ord),
        mesh=mesh, n_keep=n_keep, src_pad=max(int(counts.max()), 1),
        bucket=bucket, stride_new=stride_new, new_params=new_params,
    )
    return (
        ShardedDBLSH(index=idx, axis=axis, n_total=pn * n_keep,
                     n_local=n_keep, stride=stride_new),
        id_map,
    )
