"""The comparison that decides ``correct``.

Three numbers, each with its limit:

* ``dist_rms``: the root mean square of the relative gaps between the
  returned distances and the float64 distances from each query to the
  rows named, over every rank of every sampled answer.  A row outside
  the data, a missing answer or a non-finite distance reads as infinity.
  The limit is set between what sound float32 runs read and what the
  control reads.  (The largest gap separates the two less: it comes
  from the few nearest rows, where both are dominated by the rounding
  of the norm form's large terms.)
* ``recall``: mean recall@k of the sampled answers against the exact
  reference.  Its limit, per configuration, lies between what sound runs
  read and what they read with one of the index's hash tables dropped
  (``faults.table_dropped``), a search fault that keeps every distance
  exact and that only recall can see.
* ``hit_mismatch``: cache hits whose answer differs, bit for bit, from
  the answer the search path gave the same query at the same version.
  An exact comparison: the limit is 0.
"""

from __future__ import annotations

import numpy as np

from .reference import exact_distances, fetch_rows, knn


def dist_errors(shards, queries: np.ndarray, rows: np.ndarray, dists: np.ndarray) -> np.ndarray:
    """Relative gap of every returned distance to the float64 distance
    of the row it names; infinity for a row outside the data or a
    non-finite distance."""
    n = shards[0].shape[0] * len(shards)
    valid = (rows >= 0) & (rows < n) & np.isfinite(dists)
    exact = exact_distances(queries, fetch_rows(shards, np.where(valid, rows, 0)))
    err = np.abs(dists.astype(np.float64) - exact) / np.maximum(exact, 1e-12)
    return np.where(valid, err, np.inf)


def recall(rows: np.ndarray, truth: np.ndarray) -> float:
    k = truth.shape[1]
    return float(np.mean([len(set(a.tolist()) & set(b.tolist())) / k
                          for a, b in zip(rows, truth)]))


def compare(shards, queries, rows, dists, hit_mismatch: int, limits: dict, k: int):
    """The numbers compared, each as ``(value, limit, ok)``, and the
    verdict.  ``queries`` (m, d), ``rows`` / ``dists`` (m, k): the sampled
    answers as served."""
    truth, _ = knn(shards, queries, k)
    err = dist_errors(shards, queries, rows, dists)
    numbers = {
        "dist_rms": (float(np.sqrt(np.mean(np.square(err)))), limits["dist_rms_max"], "max"),
        "recall": (recall(rows, truth), limits["recall_min"], "min"),
        "hit_mismatch": (float(hit_mismatch), 0.0, "max"),
    }
    out = {}
    for name, (value, limit, kind) in numbers.items():
        ok = limit is not None and (value <= limit if kind == "max" else value >= limit)
        out[name] = {"value": value, "limit": limit, "ok": bool(ok)}
    finite = err[np.isfinite(err)]
    spread = {"p50": float(np.median(finite)) if finite.size else None,
              "p99": float(np.percentile(finite, 99)) if finite.size else None,
              "max": float(err.max())}
    return out, all(v["ok"] for v in out.values()), spread
