"""The control and the planted faults that the comparison in ``check.py``
must catch.  Each is a function ``(col, svc, shards)`` that
``harness.run_cell(fault=...)`` calls after the build and before the
warm-up; it breaks the timed path underneath the service, which then
runs the rest of the run as usual.  The benchmark's own runs never call
them: ``readings.py`` (the control, on the chip) and the tests do.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from . import reference


def _gids(col, rows: np.ndarray) -> np.ndarray:
    """Ids the collection would return for data ``rows``: the rows
    themselves on one device, strided global ids when sharded."""
    s = getattr(col, "sharded", None)
    if s is None:
        return rows
    return (rows // s.n_local) * s.stride + rows % s.n_local


def _wrap(col, change):
    """Replace ``col.search`` by ``change(Q, out)`` of its own result."""
    search = col.search

    def broken(Q, *args, **kw):
        return change(np.asarray(Q), search(Q, *args, **kw))

    col.search = broken


def control_for(config: dict):
    """The control of a configuration: the reference put in the program's
    place, one precision below the configuration's (``correct.control``:
    ``high``, three bfloat16 passes, below float32 at HIGHEST; ``bf16``,
    one pass, below other float32).  Each batch is answered by
    ``reference.knn`` at that precision."""
    precision = config["correct"]["control"]

    def control(col, svc, shards):
        def search(Q, k=0, *args, with_stats=False, **kw):
            Q = np.asarray(Q)
            rows, dists = reference.knn(shards, Q, k or svc.default_k, precision=precision)
            out = (jnp.asarray(dists), jnp.asarray(_gids(col, rows).astype(np.int32)))
            if with_stats:
                zero = jnp.zeros((Q.shape[0],), jnp.int32)
                out += ({"radius_steps": zero, "candidates": zero},)
            return out

        col.search = search

    return control


def altered_answer(col, svc, shards):
    """An answer altered where it is produced: the first neighbour of each
    query is replaced by the next row."""
    n = sum(s.shape[0] for s in shards)

    def change(Q, out):
        ids = out[1].at[:, 0].set((out[1][:, 0] + 1) % n)
        return (out[0], ids) + tuple(out[2:])

    _wrap(col, change)


def half_batch_left_out(col, svc, shards):
    """Half of each batch left out: the second half of its rows comes
    back unanswered."""

    def change(Q, out):
        half = max(1, Q.shape[0] // 2)
        d = out[0].at[half:].set(jnp.inf)
        return (d, out[1]) + tuple(out[2:])

    _wrap(col, change)


def stale_state(col, svc, shards):
    """A step that returns its state unchanged: each batch gets the answer
    of the previous batch of its shape."""
    last = {}

    def change(Q, out):
        prev = last.get(Q.shape[0])
        last[Q.shape[0]] = out
        return out if prev is None else prev

    _wrap(col, change)


def table_dropped(col, svc, shards):
    """The last of the index's L hash tables dropped from the search: its
    block bounding boxes are emptied, so select never picks one of its
    blocks.  The blocks the other tables pick are verified as usual, so
    every distance returned is still exact: only recall can see it."""
    sharded = getattr(col, "sharded", None)
    index = col.index if sharded is None else sharded.index
    last = (jnp.arange(index.mbr_lo.shape[0]) == index.mbr_lo.shape[0] - 1)[:, None, None]

    def empty(a, fill):
        return jax.jit(lambda a: jnp.where(last, fill, a), out_shardings=a.sharding)(a)

    index = dataclasses.replace(index, mbr_lo=empty(index.mbr_lo, jnp.inf),
                                mbr_hi=empty(index.mbr_hi, -jnp.inf))
    if sharded is None:
        col.index = index
    else:
        col.sharded = dataclasses.replace(sharded, index=index)


def cache_wrong_entry(col, svc, shards):
    """A cache hit answered with the first entry the cache ever stored."""
    get, first = svc.cache.get, []

    def wrong(key):
        entry = get(key)
        if entry is not None and not first:
            first.append(entry)
        return first[0] if entry is not None else None

    svc.cache.get = wrong


def exchange_left_out(col, svc, shards):
    """The exchange between chips left out: the sharded search's
    all-gather hands each chip its own shard's answers only.  It patches
    ``jax.lax`` for the whole process: a test runs it in one of its own."""
    P = len(shards)

    def local_only(x, axis_name, **kw):
        return jnp.broadcast_to(x[None], (P,) + x.shape)

    jax.lax.all_gather = local_only
    jax.clear_caches()  # the search may have been traced with the exchange


FAULTS = {
    "altered_answer": altered_answer,
    "half_batch_left_out": half_batch_left_out,
    "stale_state": stale_state,
    "cache_wrong_entry": cache_wrong_entry,
    "table_dropped": table_dropped,
}
