"""The plain reference: exact k nearest neighbours under L2, computed from
the benchmark's own data and nothing the program made.

Each data shard is scanned on the device that holds it, CHUNK rows at a
time, keeping a running top-(k + SLACK) by the norm form of the squared
distance.  The candidates of all shards are then re-ranked on the host
in float64 by the difference form, so the answer is exact unless a true
neighbour falls more than SLACK places in float32 rounding.

``precision="high"`` or ``"bf16"`` is the benchmark's control: the same
scan with each float32 matmul done as three bfloat16 passes (what
``Precision.HIGH`` does on a TPU) or as one pass on bfloat16 operands,
written out so that a CPU computes it alike; its own top-k is kept as
the answer and its own distances returned, with no float64 re-rank.  It
stands in for the program computing one precision below the one the
configuration states: ``high`` below float32 at HIGHEST, ``bf16`` below
other float32 (``correct.control`` in the configuration).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 1 << 16
SLACK = 10
QBLOCK = 256


def _bf16_parts(a):
    """``a`` as a bfloat16 head and a bfloat16 tail, held in float32.
    ``reduce_precision`` rounds as a cast to bfloat16 would, and XLA may
    not fold it away the way it folds a float32 -> bfloat16 -> float32
    round trip."""
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    return hi, jax.lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)


def _dot(q, x, precision: str):
    """``q @ x.T``: at HIGHEST; as the three bfloat16 passes of
    ``Precision.HIGH`` (head x head + head x tail + tail x head; the
    products of bfloat16 values are exact in float32); or as the one pass
    of bfloat16 operands (``bf16``: heads only)."""
    mm = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    if precision == "highest":
        return mm(q, x.T)
    qh, ql = _bf16_parts(q)
    xh, xl = _bf16_parts(x)
    if precision == "bf16":
        return mm(qh, xh.T)
    return mm(qh, xh.T) + mm(qh, xl.T) + mm(ql, xh.T)


@partial(jax.jit, static_argnames=("keep", "precision"))
def _scan_topk(rows, q, *, keep: int, precision: str):
    """Running top-``keep`` (squared distance, local row) of ``q`` over
    ``rows``, CHUNK rows at a time."""
    n = rows.shape[0]
    n_blk = -(-n // CHUNK)
    pad = jnp.pad(rows, ((0, n_blk * CHUNK - n), (0, 0)))
    q2 = jnp.sum(jnp.square(q), -1, keepdims=True)

    def blk(carry, i):
        best_d, best_i = carry
        x = jax.lax.dynamic_slice_in_dim(pad, i * CHUNK, CHUNK)
        d2 = q2 - 2.0 * _dot(q, x, precision) + jnp.sum(jnp.square(x), -1)
        d2 = jnp.where(i * CHUNK + jnp.arange(CHUNK) < n, d2, jnp.inf)
        neg, pos = jax.lax.top_k(-d2, keep)
        cd = jnp.concatenate([best_d, -neg], axis=1)
        ci = jnp.concatenate([best_i, pos.astype(jnp.int32) + i * CHUNK], axis=1)
        neg, pos = jax.lax.top_k(-cd, keep)
        return (-neg, jnp.take_along_axis(ci, pos, axis=1)), None

    init = (jnp.full((q.shape[0], keep), jnp.inf),
            jnp.zeros((q.shape[0], keep), jnp.int32))
    (d, i), _ = jax.lax.scan(blk, init, jnp.arange(n_blk))
    return d, i


def fetch_rows(shards, rows: np.ndarray) -> np.ndarray:
    """Host float32 vectors of global ``rows`` (any shape) of the
    row-sharded data; a row outside the data gives NaNs."""
    per = shards[0].shape[0]
    flat = np.asarray(rows, np.int64).reshape(-1)
    out = np.full((flat.size, shards[0].shape[1]), np.nan, np.float32)
    for r, shard in enumerate(shards):
        sel = np.nonzero((flat >= r * per) & (flat < (r + 1) * per))[0]
        if sel.size:
            dev = shard.devices().pop()
            local = jax.device_put(np.asarray(flat[sel] - r * per, np.int32), dev)
            out[sel] = np.asarray(jnp.take(shard, local, axis=0))
    return out.reshape(np.shape(rows) + (shards[0].shape[1],))


def exact_distances(queries: np.ndarray, vectors: np.ndarray) -> np.ndarray:
    """float64 L2 distance of each query (m, d) to its vectors (m, k, d)."""
    diff = vectors.astype(np.float64) - queries.astype(np.float64)[:, None, :]
    return np.sqrt(np.sum(np.square(diff), axis=-1))


def knn(shards, queries: np.ndarray, k: int, precision: str = "highest"):
    """(rows (m, k) int64, dists (m, k)) of each query's k nearest rows.

    ``highest``: exact, float64 distances.  ``high`` or ``bf16``: the
    control's own answer, float32 distances from its norm form at that
    precision."""
    per = shards[0].shape[0]
    keep = k + SLACK if precision == "highest" else k
    m = queries.shape[0]
    # every block of every shard is dispatched before any is read, so
    # that the shards' devices scan at once
    out = []
    for shard in shards:
        dev = shard.devices().pop()
        blocks = []
        for lo in range(0, m, QBLOCK):
            qb = np.zeros((QBLOCK, queries.shape[1]), np.float32)
            qb[: min(QBLOCK, m - lo)] = queries[lo:lo + QBLOCK]
            blocks.append(_scan_topk(shard, jax.device_put(qb, dev), keep=keep,
                                     precision=precision))
        out.append(blocks)
    cand_d = [np.concatenate([np.asarray(d) for d, _ in blocks])[:m] for blocks in out]
    cand_i = [np.concatenate([np.asarray(i, np.int64) for _, i in blocks])[:m] + r * per
              for r, blocks in enumerate(out)]
    cd, ci = np.concatenate(cand_d, axis=1), np.concatenate(cand_i, axis=1)
    if precision != "highest":
        order = np.argsort(cd, axis=1, kind="stable")[:, :k]
        d2 = np.take_along_axis(cd, order, axis=1)
        return (np.take_along_axis(ci, order, axis=1),
                np.sqrt(np.maximum(d2, 0.0)).astype(np.float32))
    exact = exact_distances(queries, fetch_rows(shards, ci))
    order = np.argsort(exact, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(ci, order, axis=1),
            np.take_along_axis(exact, order, axis=1))
