"""The one traffic generator.  A mix is a JSON file under ``traffic/``:

``loop``
    ``"closed"``: ``clients`` callers, each sending its next query when
    its previous one completes; latency runs from the submit.
    ``"open"``: ``rate_qps`` arrivals a second, Poisson, sent on their due
    times whatever the backlog; latency runs from the due time.
``tenants``
    tenant name -> weighted round-robin weight in the service; requests
    (open) or clients (closed) take the tenants in turn.
``queries``
    ``pool``: distinct queries drawn from the data's distribution;
    ``order``: ``"sequential"`` (request i asks pool row i mod pool) or
    ``"zipf"`` (Zipf with exponent ``zipf_s`` over the pool, rank r being
    pool row r - 1).

Everything drawn here comes from the run's seed: the same seed gives
the same due times and the same query order.  An open loop sends
exactly ``round(rate_qps * seconds)`` requests, at uniform times sorted
(a Poisson process given its count), so that every seed offers the same
amount of work.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

_LOOPS = ("closed", "open")
_ORDERS = ("sequential", "zipf")
_ZIPF_BLOCK = 1 << 16


def load(path: Path) -> dict:
    """Read and check a traffic file."""
    spec = json.loads(Path(path).read_text())
    if spec.get("loop") not in _LOOPS:
        raise ValueError(f"{path}: loop must be one of {_LOOPS}")
    if spec["loop"] == "closed" and int(spec.get("clients", 0)) < 1:
        raise ValueError(f"{path}: a closed loop needs clients >= 1")
    if spec["loop"] == "open" and not float(spec.get("rate_qps", 0)) > 0:
        raise ValueError(f"{path}: an open loop needs rate_qps > 0")
    tenants = spec.get("tenants") or {}
    if not tenants or any(int(w) < 1 for w in tenants.values()):
        raise ValueError(f"{path}: tenants maps names to weights >= 1")
    q = spec.get("queries") or {}
    if int(q.get("pool", 0)) < 1 or q.get("order") not in _ORDERS:
        raise ValueError(f"{path}: queries needs pool >= 1 and order in {_ORDERS}")
    if q["order"] == "zipf" and not float(q.get("zipf_s", 0)) > 0:
        raise ValueError(f"{path}: zipf order needs zipf_s > 0")
    return spec


def _rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), salt])


def tenant(spec: dict, i: int) -> str:
    """Tenant of request (open) or client (closed) ``i``."""
    names = sorted(spec["tenants"])
    return names[i % len(names)]


class QueryOrder:
    """Pool row of each successive request."""

    def __init__(self, spec: dict, seed: int):
        q = spec["queries"]
        self.pool = int(q["pool"])
        self.order = q["order"]
        self._rng = _rng(seed, 1)
        self._buf = np.zeros(0, np.int64)
        self._i = 0
        if self.order == "zipf":
            w = 1.0 / np.power(np.arange(1, self.pool + 1, dtype=np.float64),
                               float(q["zipf_s"]))
            self._cdf = np.cumsum(w / w.sum())

    def take(self, count: int) -> np.ndarray:
        """The pool rows of the next ``count`` requests."""
        if self.order == "sequential":
            rows = (self._i + np.arange(count)) % self.pool
            self._i += count
            return rows
        while self._buf.size < count:
            u = self._rng.random(_ZIPF_BLOCK)
            drawn = np.minimum(np.searchsorted(self._cdf, u, side="right"), self.pool - 1)
            self._buf = np.concatenate([self._buf, drawn])
        rows, self._buf = self._buf[:count], self._buf[count:]
        return rows


def due_times(spec: dict, seed: int, seconds: float) -> np.ndarray:
    """Offsets in seconds from the window's start at which the open
    loop's requests fall due, ascending."""
    n = int(round(float(spec["rate_qps"]) * seconds))
    return np.sort(_rng(seed, 2).uniform(0.0, seconds, n))
