"""Named vector collections: the local placement of the store lifecycle.

A :class:`Collection` owns one :class:`~repro.core.index.DBLSHIndex` plus
an optional *payload* array aligned row-for-row with the indexed vectors
(the kNN-LM "value" generalized: token ids, document ids, metadata rows —
anything that should ride along with a returned neighbor id).

The managed lifecycle itself — version bumping, the auto-compaction
policy, payload ride-along, calibration invalidation, snapshot/restore
plumbing — lives in :class:`~repro.store.lifecycle.CollectionLifecycle`,
shared with the sharded placement (``store.router.ShardedCollection``).
This class supplies the single-device mechanics over ``core.updates``:

* ``add`` / ``remove`` delegate to ``core.updates.insert`` / ``delete``;
* ``compact`` rebuilds through ``core.updates.compact`` with freshly
  derived K/L (K ~ log n was sized for the build-time ``n``, see
  DESIGN.md §3-§4);
* ``snapshot`` / ``restore`` persist the index arrays through
  ``checkpoint.Checkpointer``'s atomic step directories.

Every mutation advances a **version** drawn from a process-wide
monotonic clock — the cache-invalidation token for the store layer
(DESIGN.md §6); ``restore`` deliberately assigns a *fresh* version so
diverged histories can never alias each other's cache entries.

Repeated small ``add`` calls append padded STR blocks per call; the waste
is bounded by ``block_size - 1`` slots per add per table and is reclaimed
at the next compaction.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import Checkpointer
from ..core import DBLSHParams, build, search_batch_fixed, validate_engine
from ..core.index import (
    DBLSHIndex,
    compute_norm_blocks,
    empty_quant_blocks,
    quantize_blocks,
)
from ..core import updates as _updates
from ..tune import planner as _planner
from .lifecycle import (
    _INDEX_ARRAY_FIELDS,
    CollectionLifecycle,
    CollectionStats,
    CompactionPolicy,
    version_clock,
)

__all__ = ["CompactionPolicy", "CollectionStats", "Collection", "version_clock"]


class Collection(CollectionLifecycle):
    """A named DB-LSH index + payload with a managed lifecycle."""

    placement = "local"

    def __init__(self, name: str, index: DBLSHIndex, **kw):
        self.index = index
        super().__init__(name, **kw)

    def _validate_default_engine(self, engine: str | None) -> str | None:
        if engine is not None:
            validate_engine(engine)
            if engine == "inline" and not self.index.params.inline_vectors:
                raise ValueError(
                    f"collection {self.name!r}: engine='inline' needs an index "
                    "built with inline_vectors=True (the scalar-prefetch "
                    "kernel streams the per-table vector copy)"
                )
        return engine

    # ------------------------------------------------------------ construction
    @classmethod
    def create(
        cls,
        name: str,
        key: jax.Array,
        data,
        *,
        params: DBLSHParams | None = None,
        payload=None,
        policy: CompactionPolicy | None = None,
        engine: str | None = None,
        search_policy=None,
        **derive_kw,
    ) -> "Collection":
        """Build a fresh index over ``data`` (params derived if omitted).
        ``engine`` sets the collection's default verify engine;
        ``search_policy`` its default query-planning policy (a
        ``repro.tune`` ``RecallTarget`` / ``LatencyBudget`` /
        ``FixedSchedule`` — run :meth:`calibrate` to back the
        outcome-level policies with a measured table)."""
        data = jnp.asarray(data, jnp.float32)
        kb, kc = jax.random.split(key)
        if params is None:
            params = DBLSHParams.derive(
                n=data.shape[0], d=data.shape[1], **derive_kw
            )
        index = build(kb, data, params)
        return cls(name, index, payload=payload, policy=policy, key=kc,
                   engine=engine, search_policy=search_policy)

    @classmethod
    def from_index(
        cls, name: str, index: DBLSHIndex, *, payload=None,
        policy: CompactionPolicy | None = None, key=None,
        engine: str | None = None,
    ) -> "Collection":
        """Wrap an already-built index (e.g. a kNN-LM datastore)."""
        return cls(name, index, payload=payload, policy=policy, key=key,
                   engine=engine)

    # -------------------------------------------------------------- properties
    @property
    def n(self) -> int:
        """Indexed rows including tombstones and pre-compaction growth."""
        return self.index.n

    @property
    def d(self) -> int:
        return self.index.data.shape[1]

    def live_count(self) -> int:
        return _updates.live_count(self.index)

    # -------------------------------------------------------- placement hooks
    def _insert(self, points, payload) -> np.ndarray:
        m = points.shape[0]
        # int32 end to end: search results, id maps, and delete all speak
        # int32, so returned ids round-trip without re-casting
        ids = np.arange(self.n, self.n + m, dtype=np.int32)
        self.index = _updates.insert(self.index, points)
        if payload is not None:
            self.payload = jnp.concatenate([self.payload, payload], axis=0)
        return ids

    def _delete(self, ids) -> None:
        self.index = _updates.delete(self.index, ids)

    def _compact_impl(self, key) -> np.ndarray:
        self.index, id_map = _updates.compact(self.index, key)
        return id_map

    def _calibrate_impl(self, queries, *, k, r0, steps_max, engine,
                        interpret, measure_ms):
        # ground the oracle on live rows only: tombstoned rows cannot be
        # returned, so leaving them in would under-measure recall
        ids0 = np.asarray(self.index.ids_blocks[0])
        live = np.unique(ids0[ids0 < self.index.n])
        return _planner.calibrate(
            self.index, queries, k=k, r0=r0, steps_max=steps_max,
            engine=engine or self.default_engine or "jnp",
            interpret=interpret, measure_ms=measure_ms,
            oracle_rows=None if live.size == self.index.n else live,
        )

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
        """Batched (c,k)-ANN through the fixed-schedule serving path.

        ``engine=None`` resolves to the collection's ``default_engine``
        (falling back to 'jnp'). ``rows`` is the number of *real* query
        rows when ``Q`` carries padding (the StoreService pads to its
        fixed batch-shape menu); the query counter advances by ``rows``,
        not the padded shape.  The returned arrays are device futures —
        nothing here blocks, so a caller may overlap host work with the
        search (DESIGN.md §6).  ``with_explain`` (implies
        ``with_stats``) appends the per-query per-step EXPLAIN arrays —
        see :func:`~repro.core.serve_search.search_batch_fixed`.
        ``dtype`` ('fp32'/'bf16'/'int8') selects the distance precision;
        the quantized paths need an index built with the matching
        ``quant_dtype`` and are a shortlist + exact fp32 re-rank, so the
        returned distances are always exact fp32.
        """
        Q = self._upload(Q, rows)
        return self._dispatch(
            search_batch_fixed, self.index, Q, k=k, r0=r0, steps=steps,
            engine=engine or self.default_engine or "jnp",
            with_stats=with_stats, interpret=interpret, exact=exact,
            termination=termination, with_explain=with_explain,
            dtype=dtype,
        )

    # ------------------------------------------------------------ persistence
    def _snapshot_arrays(self) -> dict:
        return {
            f: np.asarray(getattr(self.index, f)) for f in _INDEX_ARRAY_FIELDS
        }

    def _snapshot_meta(self) -> dict:
        return {"params": dataclasses.asdict(self.index.params)}

    @classmethod
    def restore(cls, directory: str, step: int | None = None) -> "Collection":
        tree, meta = Checkpointer(directory).restore(step)
        if meta.get("placement", "local") != "local":
            raise ValueError(
                f"snapshot at {directory!r} is {meta['placement']!r}: "
                "restore it with ShardedCollection.restore(mesh=...) or "
                "repro.store.restore_collection(..., mesh=...)"
            )
        params = DBLSHParams(**meta["params"])
        arrays = {
            f: jnp.asarray(tree[f]) for f in _INDEX_ARRAY_FIELDS if f in tree
        }
        if "norm_blocks" not in arrays:
            # snapshots from before the MXU-verify norm cache: rebuild it
            # from the persisted data/ids (cheap, one reduction per point)
            arrays["norm_blocks"] = compute_norm_blocks(
                arrays["data"], arrays["ids_blocks"]
            )
        # quantized blocks are derived state, never persisted (bf16 does
        # not np.save round-trip): re-quantize from the fp32 truth
        if params.quant_dtype != "none":
            arrays["qvec_blocks"], arrays["qvec_scale"] = quantize_blocks(
                arrays["data"], arrays["ids_blocks"], params.quant_dtype
            )
        else:
            arrays["qvec_blocks"], arrays["qvec_scale"] = (
                empty_quant_blocks(params.quant_dtype)
            )
        index = DBLSHIndex(**arrays, params=params)
        return cls(meta["name"], index,
                   **cls._common_restore_kwargs(tree, meta))
