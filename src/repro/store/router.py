"""Shard-aware routing: the full Collection lifecycle over
``core.distributed``.

A dataset too large for one device shards over the mesh 'data' axis:
every device builds a local DB-LSH index with the *same* LSH functions
(``core.distributed.build_sharded``), queries replicate, and per-shard
top-k merge with one all_gather into globally-id'd results.
:class:`ShardedCollection` implements the same mutable lifecycle
protocol as a local :class:`~repro.store.collection.Collection`
(``store.lifecycle.CollectionLifecycle``): ``add`` routes inserts to the
least-loaded shard, ``remove`` translates global ids per shard,
``compact`` rebalances survivors across shards and rebuilds with a
gathered global id remap, and ``snapshot`` / ``restore(mesh=...)``
persist the whole state — elastically: a snapshot taken on P shards
restores onto any shard count — so a
:class:`~repro.store.service.StoreService` serves both placements
through one admission queue, one cache-invalidation contract, and one
policy/engine resolution path, with no read-only special cases.

:func:`open_collection` is the router decision point: it places data on
a single device when it fits (``max_points_per_shard``), otherwise fans
out over the mesh — the lifecycle options (``policy``, ``engine``,
``search_policy``) apply to whichever placement wins.

**Id contract** (DESIGN.md §9): global ids are *strided*,
``gid = rank * stride + local`` with per-shard headroom
(``stride >= n_local``, sized by the compaction policy's growth ratio).
That keeps the merge's disjoint-id invariant AND makes ids durable
handles: an ``add`` grows ``n_local`` inside the stride, so every
existing id survives untouched.  Only ``compact`` renumbers — when the
policy fires, when called explicitly, or when an ``add`` would overflow
the stride — and it returns the id map exactly like the local
placement.  Elastic ``restore`` onto a different shard count also
renumbers (the manifest's geometry is P-specific); derive fresh ids
from searches after one.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..checkpoint import Checkpointer
from ..core import DBLSHParams
from ..core.distributed import (
    ShardedDBLSH,
    _index_specs,
    build_sharded,
    compact_sharded,
    delete_sharded,
    id_stride,
    insert_sharded,
    search_sharded,
    shard_live_counts,
)
from ..core.index import DBLSHIndex, empty_quant_blocks, quantize_blocks
from ..resilience import faults
from ..tune import planner as _planner
from .collection import Collection, CompactionPolicy
from .lifecycle import _INDEX_ARRAY_FIELDS, CollectionLifecycle

__all__ = ["ShardedCollection", "open_collection"]


class ShardedCollection(CollectionLifecycle):
    """A collection fanned out over the mesh ``axis`` — same mutable
    lifecycle as :class:`~repro.store.collection.Collection`.

    The payload stays global (replicated): it is indexed by *global*
    ids after the top-k merge, which is exactly what ``search_sharded``
    returns.  Mutations draw versions from the same process-wide clock
    as local collections, so the service result cache invalidates
    sharded updates identically (DESIGN.md §6).
    """

    placement = "sharded"

    def __init__(self, name: str, sharded: ShardedDBLSH, mesh, **kw):
        self.sharded = sharded
        self.mesh = mesh
        # the sharded path always verifies through the jnp engine;
        # ``fixed_engine`` tells the StoreService's engine resolution to
        # ignore request/collection/service preferences entirely, so
        # tickets and cache keys reflect the engine that actually ran
        # (and a drained batch is never split over engines pointlessly)
        self.fixed_engine = "jnp"
        # set transiently by _insert when a batch would overflow the id
        # stride, so the forced compact re-strides with room for it
        self._stride_reserve = 0
        payload = kw.get("payload")
        if payload is not None:
            payload = jnp.asarray(payload)
            if (payload.shape[0] == sharded.n_total
                    and sharded.n_total != self.id_space):
                # caller handed a dense one-row-per-point payload (the
                # create() convention): expand it into the strided id
                # layout — row for gid g at buffer index g, headroom
                # holes zero
                kw = dict(kw, payload=self._expand_payload(payload))
        super().__init__(name, **kw)

    def _expand_payload(self, dense: jax.Array) -> jax.Array:
        """Dense (n_total, ...) payload -> strided (id_space, ...)."""
        s = self.sharded
        row = np.arange(s.n_total)
        gid = (row // s.n_local) * s.stride + row % s.n_local
        buf = jnp.zeros((self.id_space,) + dense.shape[1:], dense.dtype)
        return buf.at[jnp.asarray(gid)].set(dense)

    def _validate_default_engine(self, engine: str | None) -> str | None:
        if engine not in (None, "jnp"):
            raise ValueError(
                f"collection {self.name!r}: sharded collections verify per "
                f"shard through the jnp engine; engine={engine!r} cannot be "
                "honored (fixed_engine pins service resolution)"
            )
        return engine

    @classmethod
    def create(
        cls,
        name: str,
        key: jax.Array,
        data,
        mesh,
        *,
        axis: str = "data",
        params: DBLSHParams | None = None,
        payload=None,
        policy: CompactionPolicy | None = None,
        engine: str | None = None,
        search_policy=None,
        **derive_kw,
    ) -> "ShardedCollection":
        # place each shard's rows straight onto its device: the global
        # array never sits whole on one device
        data = jax.device_put(
            data if isinstance(data, jax.Array) else np.asarray(data),
            NamedSharding(mesh, P(axis)),
        ).astype(jnp.float32)
        n, d = data.shape
        pn = mesh.shape[axis]
        if params is None:
            # size K/L for the per-shard n: each device answers locally.
            params = DBLSHParams.derive(n=n // pn, d=d, **derive_kw)
        # id stride with insert headroom: the growth trigger fires at
        # growth_ratio * built n, so sizing the stride to the same ratio
        # means a well-behaved policy compacts before the stride ever
        # forces a renumber
        pol = policy or CompactionPolicy()
        stride = id_stride(n // pn, cls._headroom(pol))
        sharded = build_sharded(key, data, params, mesh, axis=axis,
                                stride=stride)
        # build consumes the caller's key whole (identical hash functions
        # on every shard); fold for the compaction key stream instead of
        # splitting so the built index matches a local build(key, ...)
        kc = jax.random.fold_in(key, 0x5EED)
        return cls(name, sharded, mesh, payload=payload, policy=policy,
                   key=kc, engine=engine, search_policy=search_policy)

    @staticmethod
    def _headroom(policy: CompactionPolicy) -> float:
        """Stride headroom factor: track the growth trigger, floored so
        a no-growth policy still leaves real insert room."""
        return max(float(policy.growth_ratio), 1.25)

    # ---------------------------------------------------------------- surface
    @property
    def n(self) -> int:
        return self.sharded.n_total

    @property
    def d(self) -> int:
        return self.sharded.index.data.shape[1]

    @property
    def id_space(self) -> int:
        return self.sharded.id_space

    def live_count(self) -> int:
        return int(np.asarray(shard_live_counts(self.sharded, self.mesh)).sum())

    def shard_counts(self) -> np.ndarray:
        """Per-shard live point counts (P,) — the insert-routing signal."""
        return np.asarray(shard_live_counts(self.sharded, mesh=self.mesh))

    def _occupancy(self) -> tuple[int, int]:
        counts = self.shard_counts()  # one device read serves both
        live = int(counts.sum())
        pn = int(counts.shape[0])
        # compaction rebalances, so the attainable n is the balanced
        # ceiling — imbalance alone now justifies a rebuild when it
        # leaves the fleet hollow enough to trip the policy
        return live, pn * -(-live // pn)

    # -------------------------------------------------------- placement hooks
    def _insert(self, points, payload) -> np.ndarray:
        m = int(points.shape[0])
        if self.sharded.n_local + m > self.sharded.stride:
            # the stride is the id contract's renumbering boundary: ids
            # are stable until the headroom is exhausted, then one
            # compact() renumbers (returning the id map through the
            # normal add/remove channels) and re-strides with room for
            # this batch
            self._stride_reserve = m
            try:
                self.compact()
            finally:
                self._stride_reserve = 0
        counts = self.shard_counts()
        target = int(np.argmin(counts))  # least-loaded shard takes the batch
        s = self.sharded
        n_old = s.n_local
        self.sharded = insert_sharded(s, points, target, mesh=self.mesh)
        base = target * s.stride + n_old
        if self.payload is not None:
            # ids are stable, so the strided payload layout is too: the
            # batch lands in the target's headroom — one in-place tail
            # write instead of re-slotting every shard's block
            self.payload = self.payload.at[base:base + m].set(
                jnp.asarray(payload)
            )
        return base + np.arange(m, dtype=np.int32)

    def _delete(self, ids) -> None:
        self.sharded = delete_sharded(self.sharded, ids, mesh=self.mesh)

    def _compact_impl(self, key) -> np.ndarray:
        self.sharded, id_map = compact_sharded(
            self.sharded, key, self.mesh,
            headroom=self._headroom(self.policy),
            reserve=self._stride_reserve,
        )
        return id_map

    def _calibrate_impl(self, queries, *, k, r0, steps_max, engine,
                        interpret, measure_ms):
        del engine, interpret  # per-shard verify is pinned to jnp
        kk = k or self.sharded.index.params.k

        def search_fn(Q, r0, steps, with_stats=False):
            return search_sharded(
                self.sharded, Q, k=kk, r0=r0, steps=steps, mesh=self.mesh,
                with_stats=with_stats,
            )

        rows, gids = self._live_rows_and_ids()
        return _planner.calibrate(
            self.sharded.index, queries, k=kk, r0=r0, steps_max=steps_max,
            measure_ms=measure_ms, search_fn=search_fn,
            oracle_rows=rows, oracle_ids=gids,
        )

    def _live_rows_and_ids(self) -> tuple[np.ndarray | None, np.ndarray | None]:
        """Live points as ``(data_rows, gids)`` — the calibration oracle
        needs both: brute force runs over data *rows* while the search
        reports strided *gids*, and the two spaces coincide only in the
        dense, fully-live case (then ``(None, None)``: use everything).
        The oracle must exclude dead rows: a sharded insert leaves P-1
        tombstoned replicas of every point at identical coordinates, and
        compaction padding adds zero rows — none of them returnable."""
        s = self.sharded
        pn = int(self.mesh.shape[s.axis])
        ids0 = np.asarray(s.index.ids_blocks[0])  # (nb_global, B) local ids
        blocks = ids0.reshape(pn, -1)
        rows, gids = [], []
        for r in range(pn):
            loc = np.unique(blocks[r])
            loc = loc[loc < s.n_local]
            rows.append(loc + r * s.n_local)
            gids.append(loc + r * s.stride)
        rows = np.concatenate(rows)
        if rows.size == s.n_total and s.stride == s.n_local:
            return None, None
        return rows, np.concatenate(gids)

    # ------------------------------------------------------------------ reads
    def search(
        self,
        Q,
        k: int = 0,
        *,
        r0: float = 1.0,
        steps: int = 8,
        engine: str | None = None,
        with_stats: bool = False,
        interpret: bool | None = None,
        rows: int | None = None,
        exact: bool = False,
        termination=None,
        with_explain: bool = False,
        dtype: str = "fp32",
    ):
        """Global (c,k)-ANN: per-shard fixed-schedule search + all_gather
        top-k merge. ``engine`` / ``interpret`` are accepted for API
        parity; the sharded path always verifies through the jnp engine.
        ``rows`` (real rows in a service-padded batch) advances the query
        counter like the local placement.  With ``with_stats`` the
        per-shard probe statistics survive the collective merge
        (``search_sharded`` aggregates candidates by psum and
        radius_steps by pmax), so ``svc.stats()`` reports real per-query
        probe effort for sharded collections.  ``termination`` applies
        per shard (each device runs its own C1/C2 masks and while_loop
        exit — see ``search_sharded``).  ``with_explain`` appends the
        per-step EXPLAIN arrays *with per-shard attribution* (steps /
        slots / cause per shard, gathered before the pmax/psum
        collapse — see ``search_sharded``).  ``dtype`` selects the
        per-shard distance precision ('fp32'/'bf16'/'int8'): each shard
        runs the quantized shortlist + exact re-rank locally, so the
        all_gather merge always compares fp32 distances."""
        del engine, interpret
        # onto one device; search_sharded replicates it over the mesh
        Q = self._upload(Q, rows)
        k = k or self.sharded.index.params.k
        # shard.straggle: one slow shard stalls the all_gather merge —
        # injected here (a no-op without an installed FaultPlan) so the
        # service's EWMA straggler monitor sees it as a slow batch
        faults.fire("shard.straggle", collection=self.name, scale=steps)
        return self._dispatch(
            search_sharded, self.sharded, Q, k=k, r0=r0, steps=steps,
            mesh=self.mesh, with_stats=with_stats, exact=exact,
            termination=termination, with_explain=with_explain, dtype=dtype,
        )

    # ------------------------------------------------------------ persistence
    def _snapshot_arrays(self) -> dict:
        # np.asarray gathers each sharded array to one host copy — the
        # manifest stores the *global* layout plus the shard geometry
        # (shards / n_local / stride) needed either to re-place it
        # bit-for-bit on an equal mesh or to migrate it onto a different
        # shard count (elastic restore).
        return {
            f: np.asarray(getattr(self.sharded.index, f))
            for f in _INDEX_ARRAY_FIELDS
        }

    def _snapshot_meta(self) -> dict:
        return {
            "params": dataclasses.asdict(self.sharded.index.params),
            "axis": self.sharded.axis,
            "shards": int(self.mesh.shape[self.sharded.axis]),
            "n_local": self.sharded.n_local,
            "n_total": self.sharded.n_total,
            "stride": self.sharded.stride,
        }

    @classmethod
    def restore(
        cls, directory: str, *, mesh, step: int | None = None,
        migrate: bool | None = None,
    ) -> "ShardedCollection":
        """Re-place a sharded snapshot onto ``mesh``.

        On an equal shard count the persisted per-shard layout is
        device_put back verbatim (bit-identical restore).  Onto a
        *different* shard count the fleet is elastic: live rows are
        extracted from the manifest, re-partitioned balanced over the
        new mesh (the same balanced-contiguous split compaction uses),
        and rebuilt per shard — which renumbers global ids and
        invalidates any fitted calibration (derive fresh ids from
        searches; re-calibrate for planning).  ``migrate=True`` forces
        the migration path even at equal shard counts (a rebalancing
        restore); ``migrate=False`` demands the bit-identical path and
        raises on a shard-count mismatch."""
        tree, meta = Checkpointer(directory).restore(step)
        if meta.get("placement", "local") != "sharded":
            raise ValueError(
                f"snapshot at {directory!r} is local: restore it with "
                "Collection.restore() or repro.store.restore_collection()"
            )
        axis = meta["axis"]
        pn = int(meta["shards"])
        if migrate is None:
            migrate = int(mesh.shape[axis]) != pn
        if migrate:
            return cls._restore_migrated(tree, meta, mesh)
        if mesh.shape[axis] != pn:
            raise ValueError(
                f"snapshot was taken on {pn} shards over {axis!r} but the "
                f"mesh has {mesh.shape[axis]} and migrate=False: the "
                "per-shard layout is P-specific — allow migration or "
                "restore onto an equal mesh"
            )
        params = DBLSHParams(**meta["params"])
        specs = _index_specs(axis, params)
        arrays = {
            f: jax.device_put(
                np.asarray(tree[f]), NamedSharding(mesh, getattr(specs, f))
            )
            for f in _INDEX_ARRAY_FIELDS
            if f in tree
        }
        # Quantized blocks are derived state (never persisted): rebuild
        # them per shard on host — ids_blocks values are *shard-local*
        # row indices, so a single global quantize_blocks over the
        # concatenated manifest would gather the wrong rows for every
        # shard past rank 0.
        if params.quant_dtype != "none":
            n_local = int(meta["n_local"])
            datah = np.asarray(tree["data"]).reshape(pn, n_local, -1)
            idsh = np.asarray(tree["ids_blocks"])  # (L, nb_global, B)
            sb = idsh.shape[1] // pn
            qb_parts, qs_parts = [], []
            for r in range(pn):
                qb, qs = quantize_blocks(
                    jnp.asarray(datah[r]),
                    jnp.asarray(idsh[:, r * sb:(r + 1) * sb]),
                    params.quant_dtype,
                )
                qb_parts.append(qb)
                qs_parts.append(qs)
            arrays["qvec_blocks"] = jax.device_put(
                jnp.concatenate(qb_parts, axis=1),
                NamedSharding(mesh, specs.qvec_blocks),
            )
            arrays["qvec_scale"] = jax.device_put(
                jnp.concatenate(qs_parts, axis=1),
                NamedSharding(mesh, specs.qvec_scale),
            )
        else:
            qb, qs = empty_quant_blocks(params.quant_dtype)
            arrays["qvec_blocks"] = jax.device_put(
                qb, NamedSharding(mesh, specs.qvec_blocks))
            arrays["qvec_scale"] = jax.device_put(
                qs, NamedSharding(mesh, specs.qvec_scale))
        index = DBLSHIndex(**arrays, params=params)
        sharded = ShardedDBLSH(
            index=index, axis=axis, n_total=int(meta["n_total"]),
            n_local=int(meta["n_local"]),
            # pre-stride snapshots carry dense ids
            stride=int(meta.get("stride", meta["n_local"])),
        )
        return cls(meta["name"], sharded, mesh,
                   **cls._common_restore_kwargs(tree, meta))

    @classmethod
    def _restore_migrated(cls, tree, meta, mesh) -> "ShardedCollection":
        """Elastic restore: manifest rows -> balanced rebuild on ``mesh``.

        Survivor extraction and re-partitioning run on host from the
        gathered manifest (restore already has the host copy); the
        balanced split is the one :func:`compact_sharded` uses, so the
        post-restore fleet meets the same imbalance bound (counts differ
        by at most 1).  Global ids are renumbered; payload rows follow
        their points through the old->new gid map."""
        axis = meta["axis"]
        pn_old = int(meta["shards"])
        n_local = int(meta["n_local"])
        stride_old = int(meta.get("stride", n_local))
        pn = int(mesh.shape[axis])
        p_old = DBLSHParams(**meta["params"])
        # live (local id, data row, gid) per old shard, from table 0 of
        # the persisted blocks — ascending gid order, like compaction
        blocks = np.asarray(tree["ids_blocks"])[0].reshape(pn_old, -1)
        data = np.asarray(tree["data"]).reshape(pn_old, n_local, -1)
        rows, old_gids = [], []
        for r in range(pn_old):
            loc = np.unique(blocks[r])
            loc = loc[loc < n_local]
            rows.append(data[r, loc])
            old_gids.append(loc + r * stride_old)
        surv = np.concatenate(rows)
        old_gids = np.concatenate(old_gids)
        total = int(surv.shape[0])
        if total == 0:
            raise ValueError("restore: snapshot holds no live points")
        base, rem = divmod(total, pn)
        targets = base + (np.arange(pn) < rem)
        n_keep = int(targets.max())
        kw = cls._common_restore_kwargs(tree, meta)
        stride = id_stride(n_keep, cls._headroom(kw["policy"]))
        dst_off = np.concatenate([[0], np.cumsum(targets)])
        padded = np.zeros((pn * n_keep, surv.shape[1]), np.float32)
        new_gids = np.empty(total, np.int64)
        for r in range(pn):
            seg = surv[dst_off[r]:dst_off[r + 1]]
            padded[r * n_keep:r * n_keep + seg.shape[0]] = seg
            new_gids[dst_off[r]:dst_off[r + 1]] = (
                r * stride + np.arange(seg.shape[0])
            )
        params = p_old.rebuilt(n_keep)
        kw["key"], kb = jax.random.split(kw["key"])
        sharded = build_sharded(kb, jnp.asarray(padded), params, mesh,
                                axis=axis, stride=stride)
        pad_gids = np.concatenate([
            r * stride + np.arange(int(targets[r]), n_keep) for r in range(pn)
        ])
        if pad_gids.size:
            sharded = delete_sharded(
                sharded, jnp.asarray(pad_gids, jnp.int32), mesh=mesh
            )
        if kw["payload"] is not None:
            pay = np.asarray(kw["payload"])
            buf = np.zeros((pn * stride,) + pay.shape[1:], pay.dtype)
            buf[new_gids] = pay[old_gids]
            kw["payload"] = jnp.asarray(buf)
        # the geometry changed: the old growth baseline and fitted
        # schedule table describe an index that no longer exists
        kw["built_n"] = pn * n_keep
        kw["calibration"] = None
        return cls(meta["name"], sharded, mesh, **kw)


def open_collection(
    name: str,
    key: jax.Array,
    data,
    *,
    mesh=None,
    axis: str = "data",
    max_points_per_shard: int = 1_000_000,
    payload=None,
    policy: CompactionPolicy | None = None,
    engine: str | None = None,
    search_policy=None,
    **derive_kw,
):
    """Route a dataset to local or sharded placement.

    Local :class:`Collection` when ``data`` fits one device (or no mesh
    given); :class:`ShardedCollection` fan-out otherwise.  The lifecycle
    options apply to either placement: ``policy`` drives auto-compaction
    of sharded collections exactly as it does local ones, and
    ``search_policy`` rides into the service's plan resolution.
    ``engine`` must be None or 'jnp' on the sharded path (per-shard
    verification is pinned to jnp) — it is validated, never silently
    dropped.
    """
    # np.shape reads the shape attribute without materializing: routing
    # must never gather a device-sharded array to host just to count it
    n = np.shape(data)[0]
    if mesh is not None and mesh.shape[axis] > 1 and n > max_points_per_shard:
        return ShardedCollection.create(
            name, key, data, mesh, axis=axis, payload=payload, policy=policy,
            engine=engine, search_policy=search_policy, **derive_kw
        )
    return Collection.create(
        name, key, data, payload=payload, policy=policy, engine=engine,
        search_policy=search_policy, **derive_kw
    )
