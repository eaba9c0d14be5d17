"""The collection lifecycle protocol: one mutable contract, two placements.

Historically ``Collection`` (single-device) owned the whole lifecycle —
add / remove / auto-compaction / calibrate / snapshot / restore — while
``ShardedCollection`` was a build-once read replica.  That split leaked
"sharded is different" branches everywhere a collection was consumed.
This module extracts the placement-independent machinery into
:class:`CollectionLifecycle`, which both placements now implement:

* **version bumping** — every mutation draws a fresh version from the
  process-wide :data:`version_clock`, the cache-invalidation token the
  service layer keys on (DESIGN.md §6);
* **compaction accounting** — :class:`CompactionPolicy` triggers
  (growth past the built K/L sizing, hollowness from tombstones) and the
  ``add``/``remove``/``compact`` templates that apply them;
* **payload ride-along** — payload rows stay aligned through inserts
  and are permuted through the compaction id map (scatter by new id, so
  placements whose id space has per-shard padding holes work the same
  as the dense local layout);
* **calibration** — :meth:`calibrate` fits and stores the
  ``repro.tune`` schedule table; ``compact`` *invalidates* it (the
  rebuild re-derives K/L and reshapes the recall/cost curves) and
  auto-refits when the calibration queries were retained
  (``calibrate(..., retain=True)``) — the ROADMAP auto re-calibration
  hook;
* **snapshot / restore plumbing** — one manifest layout for both
  placements (``meta["placement"]`` tags which), persisting index
  arrays, payload, PRNG key, policy, counters, version, engine default,
  search policy, and schedule table through
  ``checkpoint.Checkpointer``'s atomic step directories.

Placements supply only the index mechanics, via the ``_insert`` /
``_delete`` / ``_compact_impl`` / ``_calibrate_impl`` /
``_snapshot_arrays`` / ``_snapshot_meta`` hooks plus the ``n`` / ``d`` /
``live_count`` / ``search`` surface.  :func:`restore_collection`
dispatches a snapshot directory to the right placement class from the
manifest alone.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import Checkpointer, CorruptSnapshot
from ..core import validate_engine
from ..obs.trace import get_tracer
from ..tune import planner as _planner
from ..tune.planner import ScheduleTable
from ..tune.policy import (
    ResolvedPlan,
    policy_from_dict,
    policy_to_dict,
    resolve_policy,
)

__all__ = [
    "CollectionLifecycle",
    "CompactionPolicy",
    "CollectionStats",
    "restore_collection",
    "version_clock",
]


class _VersionClock:
    """Process-wide monotonic source of collection versions.

    A plain per-collection counter would alias: two collections restored
    from the same snapshot both sit at version v yet may diverge, and a
    cache keyed on (name, v) would serve one the other's results.  A
    single process-wide clock makes every (mutation, restore) event
    globally unique, so version equality implies state equality.
    """

    def __init__(self):
        self._v = 0

    def next(self) -> int:
        self._v += 1
        return self._v

    def advance_past(self, v: int) -> int:
        """A fresh version strictly greater than both ``v`` and anything
        already handed out (used by restore)."""
        self._v = max(self._v, int(v))
        return self.next()


version_clock = _VersionClock()

_INDEX_ARRAY_FIELDS = (
    "proj_vecs",
    "proj_blocks",
    "ids_blocks",
    "mbr_lo",
    "mbr_hi",
    "data",
    "vec_blocks",
    "norm_blocks",
)


@dataclasses.dataclass(frozen=True)
class CompactionPolicy:
    """When to rebuild. ``auto=False`` disables the triggers (manual
    ``compact()`` still works)."""

    growth_ratio: float = 2.0    # compact when n >= ratio * last-built n
    min_live_ratio: float = 0.5  # compact when live/n drops below this
    auto: bool = True


@dataclasses.dataclass
class CollectionStats:
    inserted: int = 0
    deleted: int = 0
    compactions: int = 0
    queries: int = 0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class CollectionLifecycle:
    """Placement-independent collection lifecycle (see module doc).

    Subclasses set their index state *before* calling ``__init__`` (the
    payload-alignment assert reads ``self.n``) and implement the
    placement hooks listed in the module docstring.
    """

    #: manifest tag restore dispatches on ("local" | "sharded")
    placement = "local"

    def __init__(
        self,
        name: str,
        *,
        payload: jax.Array | np.ndarray | None = None,
        policy: CompactionPolicy | None = None,
        key: jax.Array | None = None,
        built_n: int | None = None,
        stats: CollectionStats | None = None,
        version: int | None = None,
        engine: str | None = None,
        search_policy=None,
        calibration: ScheduleTable | None = None,
    ):
        if payload is not None:
            payload = jnp.asarray(payload)
            assert payload.shape[0] == self.id_space, (
                payload.shape, self.id_space,
            )
        self.name = name
        # the tracer this collection's lifecycle and dispatch spans land
        # on: the process-global one until a StoreService attaches it
        self.tracer = get_tracer()
        self.payload = payload
        self.policy = policy or CompactionPolicy()
        self._key = jax.random.key(0) if key is None else key
        self.built_n = self.n if built_n is None else built_n
        self.stats = stats or CollectionStats()
        self.version = version_clock.next() if version is None else version
        # per-collection verify-engine default: used whenever a search /
        # service dispatch doesn't name one explicitly (None = defer to
        # the caller's default); validation is placement-specific
        self.default_engine = self._validate_default_engine(engine)
        # per-collection query-planning default (repro.tune policy): used
        # by StoreService's plan resolution whenever a submit doesn't
        # name a policy (request > collection > service); the calibration
        # table backs RecallTarget/LatencyBudget planning and persists
        # through snapshot/restore.
        self.search_policy = search_policy
        self.calibration = calibration
        self._calib_queries: np.ndarray | None = None
        self._calib_kw: dict = {}

    # -------------------------------------------------------- placement hooks
    def _validate_default_engine(self, engine: str | None) -> str | None:
        if engine is not None:
            validate_engine(engine)
        return engine

    def _insert(self, points, payload) -> np.ndarray:
        """Grow the index (and payload) by ``points``; return their
        global ids (pre-compaction)."""
        raise NotImplementedError

    def _delete(self, ids) -> None:
        """Tombstone global ``ids`` in the index."""
        raise NotImplementedError

    def _compact_impl(self, key) -> np.ndarray:
        """Rebuild the index from survivors with ``key``; return the
        global id map (n_old,): old id -> new id, or -1 if deleted.  New
        ids must ascend with old ids so the payload permute in
        :meth:`compact` stays order-preserving."""
        raise NotImplementedError

    def _calibrate_impl(self, queries, **kw) -> ScheduleTable:
        raise NotImplementedError

    def _snapshot_arrays(self) -> dict:
        """Host copies of the index arrays, keyed by field name."""
        raise NotImplementedError

    def _snapshot_meta(self) -> dict:
        """Placement-specific manifest entries (params + layout)."""
        raise NotImplementedError

    def live_count(self) -> int:
        raise NotImplementedError

    @property
    def id_space(self) -> int:
        """Exclusive upper bound of the global id space — every id that
        ``add`` or ``search`` returns is below it, and the payload buffer
        has exactly this many rows.  Dense placements equal ``n``;
        strided (sharded) placements leave per-shard insert headroom, so
        it can exceed ``n``."""
        return self.n

    # ----------------------------------------------------------------- writes
    def add(self, points, payload=None) -> np.ndarray:
        """Insert ``points`` (m, d); returns their ids (post-compaction
        ids if the policy fired)."""
        points = jnp.atleast_2d(jnp.asarray(points, jnp.float32))
        if (payload is None) != (self.payload is None):
            raise ValueError(
                f"collection {self.name!r}: payload must be provided iff the "
                "collection carries one"
            )
        if payload is not None:
            payload = jnp.asarray(payload)
            if payload.shape[0] != points.shape[0]:
                raise ValueError(
                    f"collection {self.name!r}: payload rows "
                    f"({payload.shape[0]}) != inserted points "
                    f"({points.shape[0]})"
                )
        # lifecycle mutations record on the collection's tracer (the
        # serving service's once attached; TID_LIFECYCLE lane), so a
        # serving-stack trace shows mutations interleaved with the
        # batches they invalidate
        with self.tracer.span(
            "lifecycle.add", cat="lifecycle", collection=self.name,
            placement=self.placement, rows=int(points.shape[0]),
        ) as sp:
            ids = self._insert(points, payload)
            self.stats.inserted += int(points.shape[0])
            self.version = version_clock.next()
            sp.set(version=self.version)
            id_map = self._maybe_compact()
            if id_map is not None:
                ids = id_map[ids]
                sp.set(compacted=True)
        return ids

    def remove(self, ids) -> np.ndarray | None:
        """Tombstone ``ids``; space is reclaimed at the next compaction.

        Returns the compaction id map (old id -> new id, -1 if deleted)
        when the policy fired — every outstanding id must be remapped
        through it — or None when no compaction happened."""
        ids = jnp.atleast_1d(jnp.asarray(ids, jnp.int32))
        with self.tracer.span(
            "lifecycle.remove", cat="lifecycle", collection=self.name,
            placement=self.placement, rows=int(ids.shape[0]),
        ) as sp:
            self._delete(ids)
            self.stats.deleted += int(ids.shape[0])
            self.version = version_clock.next()
            sp.set(version=self.version)
            id_map = self._maybe_compact()
            if id_map is not None:
                sp.set(compacted=True)
        return id_map

    # ------------------------------------------------------------- compaction
    def _occupancy(self) -> tuple[int, int]:
        """``(live, attainable_n)`` from one device read — the live
        point count and the smallest ``n`` a :meth:`compact` could reach
        right now.  Local compaction shrinks to the live count; sharded
        placements floor at ``P * max_shard(live)`` (SPMD shapes stay
        uniform, so per-shard padding under the fleet max is
        structural)."""
        live = self.live_count()
        return live, live

    def should_compact(self) -> bool:
        n = self.n
        if n >= self.policy.growth_ratio * self.built_n and n > self.built_n:
            return True
        live, attainable = self._occupancy()
        if live >= self.policy.min_live_ratio * n:
            return False
        # hollow — but only rebuild if compaction can actually shrink the
        # index: a sharded fleet whose imbalance (not tombstones) causes
        # the low live ratio would otherwise re-trigger on every mutation
        # and thrash through full rebuilds that change nothing
        return attainable < n

    def compact(self) -> np.ndarray:
        """Rebuild now. Returns id_map (n_old,): old id -> new id or -1.

        Invalidates the fitted schedule table (the rebuild re-derives
        K/L, which shifts the recall/cost curves) and re-fits it when
        the calibration queries were retained (``calibrate(...,
        retain=True)``)."""
        with self.tracer.span(
            "lifecycle.compact", cat="lifecycle", collection=self.name,
            placement=self.placement, n_before=int(self.n),
        ) as sp:
            id_map = self._compact_traced(sp)
        return id_map

    def _compact_traced(self, sp) -> np.ndarray:
        self._key, kc = jax.random.split(self._key)
        id_map = np.asarray(self._compact_impl(kc))
        if self.payload is not None:
            live_old = np.flatnonzero(id_map >= 0)
            pay = np.asarray(self.payload)
            # scatter each surviving row to its new id: for the dense
            # local layout this is exactly the ascending gather
            # pay[live_old]; strided/sharded layouts leave per-shard
            # padding and headroom holes, which stay zero and are never
            # returned (their ids are tombstoned or unallocated).
            buf = np.zeros((self.id_space,) + pay.shape[1:], pay.dtype)
            buf[id_map[live_old]] = pay[live_old]
            self.payload = jnp.asarray(buf)
        self.built_n = self.n
        self.stats.compactions += 1
        self.version = version_clock.next()
        sp.set(n_after=int(self.n), version=self.version)
        if self.calibration is not None or self._calib_queries is not None:
            self.calibration = None  # stale: K/L and block geometry changed
            if self._calib_queries is not None:
                self.calibrate(self._calib_queries, retain=True,
                               **self._calib_kw)
        return id_map

    def _maybe_compact(self) -> np.ndarray | None:
        if self.policy.auto and self.should_compact():
            return self.compact()
        return None

    # ----------------------------------------------------------- planning
    def calibrate(
        self,
        queries,
        *,
        k: int = 0,
        r0: float | None = None,
        steps_max: int = 8,
        engine: str | None = None,
        interpret: bool | None = None,
        measure_ms: bool = False,
        retain: bool = False,
    ) -> ScheduleTable:
        """Fit (and store) the collection's schedule table from a
        held-out query sample — the planner backing for outcome-level
        policies.  The table persists through :meth:`snapshot` /
        :meth:`restore`.  With ``retain=True`` the queries (and fit
        settings) are kept host-side and :meth:`compact` re-fits the
        table automatically after every rebuild; without it, compaction
        just invalidates (re-run calibrate by hand).  Retained queries
        do not ride in snapshots — only the fitted table does."""
        kw = dict(k=k, r0=r0, steps_max=steps_max, engine=engine,
                  interpret=interpret, measure_ms=measure_ms)
        with self.tracer.span(
            "lifecycle.calibrate", cat="lifecycle", collection=self.name,
            placement=self.placement, steps_max=steps_max,
        ):
            table = self._calibrate_impl(queries, **kw)
        self.calibration = table
        if retain:
            self._calib_queries = np.asarray(queries, np.float32)
            self._calib_kw = kw
        return table

    def plan(self, policy=None, *, default_r0: float = 1.0,
             default_steps: int = 8) -> ResolvedPlan:
        """Resolve a query-planning policy (explicit > collection
        default) against the stored calibration into the concrete
        (r0, steps, termination) the dispatch runs."""
        return _planner.plan(
            self.calibration,
            resolve_policy(policy, self.search_policy),
            default_r0=default_r0, default_steps=default_steps,
        )

    # ------------------------------------------------------------------ reads
    # A service's issue stage calls ``search`` inside
    # ``tracer.children(issue_id, lane)``, so the two spans below record
    # as children of its ``batch.issue``.
    def _upload(self, Q, rows: int | None) -> jax.Array:
        """``Q`` onto the device as a float32 matrix, counted in the
        collection's stats (``rows`` real rows of a padded batch);
        recorded as ``issue.upload``."""
        tr = self.tracer
        t0 = tr.clock() if tr.enabled else 0.0
        Q = jnp.atleast_2d(jnp.asarray(Q, jnp.float32))
        self.stats.queries += int(Q.shape[0]) if rows is None else int(rows)
        if tr.enabled:
            tr.add_span("issue.upload", t0, tr.clock(), cat="batch",
                        **tr.scope)
        return Q

    def _dispatch(self, search, *args, **kw):
        """``search(*args, **kw)``, the placement's search program, until
        it returns device futures; recorded as ``issue.dispatch``."""
        tr = self.tracer
        if not tr.enabled:
            return search(*args, **kw)
        t0 = tr.clock()
        out = search(*args, **kw)
        tr.add_span("issue.dispatch", t0, tr.clock(), cat="batch", **tr.scope)
        return out

    def get_payload(self, ids):
        """Payload rows for returned neighbor ids.

        Out-of-range ids clamp on *both* ends: the unfilled-slot sentinel
        (``id_space``) clamps to the last payload row and a negative id
        (e.g. -1 from a compaction id map marking a deleted point) clamps
        to row 0 instead of silently wrapping to the tail.  Clamped rows
        are arbitrary, not an error — always mask on the distances (+inf
        marks unfilled slots) or on ``id_map >= 0``, not on ids."""
        if self.payload is None:
            raise ValueError(f"collection {self.name!r} has no payload")
        ids = jnp.asarray(ids)
        return jnp.take(
            self.payload, jnp.clip(ids, 0, self.payload.shape[0] - 1), axis=0
        )

    # ------------------------------------------------------------ persistence
    def snapshot(self, directory: str, step: int | None = None) -> int:
        """Atomic checkpoint via Checkpointer; returns the step written.
        Defaults to one past the latest step already in ``directory`` so
        successive snapshots never overwrite each other (Checkpointer
        keeps the most recent few and GCs the rest)."""
        with self.tracer.span(
            "lifecycle.snapshot", cat="lifecycle", collection=self.name,
            placement=self.placement,
        ) as sp:
            step = self._snapshot_traced(directory, step, sp)
        return step

    def _snapshot_traced(self, directory, step, sp) -> int:
        ck = Checkpointer(directory)
        if step is None:
            latest = ck.latest_step()
            step = 0 if latest is None else latest + 1
        sp.set(step=step)
        tree = dict(self._snapshot_arrays())
        tree["prng_key"] = np.asarray(jax.random.key_data(self._key))
        if self.payload is not None:
            tree["payload"] = np.asarray(self.payload)
        meta = {
            "name": self.name,
            "placement": self.placement,
            "policy": dataclasses.asdict(self.policy),
            "built_n": self.built_n,
            "stats": self.stats.as_dict(),
            "has_payload": self.payload is not None,
            "version": self.version,
            "engine": self.default_engine,
            "search_policy": policy_to_dict(self.search_policy),
            "calibration": (
                None if self.calibration is None else self.calibration.to_dict()
            ),
            **self._snapshot_meta(),
        }
        ck.save(step, tree, meta)
        return step

    @staticmethod
    def _common_restore_kwargs(tree, meta) -> dict:
        """The lifecycle half of a restore: everything except the index
        arrays themselves.  The version is deliberately *fresh* — past
        both the persisted one and everything the process has handed out
        — so two collections diverging from one snapshot (or a restore
        racing live updates) can never alias each other's cache entries
        (DESIGN.md §6)."""
        return dict(
            payload=(
                jnp.asarray(tree["payload"]) if meta["has_payload"] else None
            ),
            policy=CompactionPolicy(**meta["policy"]),
            key=jax.random.wrap_key_data(jnp.asarray(tree["prng_key"])),
            built_n=meta["built_n"],
            stats=CollectionStats(**meta["stats"]),
            version=version_clock.advance_past(meta.get("version", 0)),
            engine=meta.get("engine"),
            search_policy=policy_from_dict(meta.get("search_policy")),
            calibration=(
                ScheduleTable.from_dict(meta["calibration"])
                if meta.get("calibration") else None
            ),
        )


def restore_collection(directory: str, step: int | None = None, *, mesh=None):
    """Restore whichever placement a snapshot holds.

    Reads the manifest alone (no array loads) to dispatch: local
    snapshots return a :class:`~repro.store.collection.Collection`;
    sharded ones need ``mesh=`` and return a
    :class:`~repro.store.router.ShardedCollection` placed on it — on any
    shard count: a mesh differing from the snapshot's triggers the
    elastic migration path (see ``ShardedCollection.restore``).

    Crash safety: with ``step=None`` this walks the directory's steps
    newest-first (the ``LATEST`` designee first) and falls back past any
    snapshot that fails integrity verification (torn write, bit-rot,
    garbled manifest — :class:`~repro.checkpoint.CorruptSnapshot`) to
    the newest step that restores cleanly.  An explicit ``step`` is
    strict: its corruption propagates."""
    ck = Checkpointer(directory)
    candidates = ck._candidate_steps(step)
    if not candidates:
        raise FileNotFoundError(f"no checkpoints in {directory}")
    last_err: Exception | None = None
    for s in candidates:
        try:
            meta, s = ck.read_meta(s)
            if meta.get("placement", "local") == "sharded":
                if mesh is None:
                    raise ValueError(
                        f"snapshot at {directory!r} is sharded "
                        f"({meta.get('shards')} shards): pass mesh= to place it"
                    )
                from .router import ShardedCollection

                return ShardedCollection.restore(directory, mesh=mesh, step=s)
            from .collection import Collection

            return Collection.restore(directory, s)
        except (CorruptSnapshot, FileNotFoundError, OSError) as e:
            last_err = e
            if step is not None:
                raise
    raise last_err
