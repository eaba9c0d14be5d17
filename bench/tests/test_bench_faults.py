"""The comparison that decides ``correct`` fails the control and every
planted fault: each breaks the timed path underneath the service, and
the rest of a run goes on as usual."""

import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import check, faults, harness  # noqa: E402
from bench.data import make_vectors  # noqa: E402
from bench.tests import tiny  # noqa: E402

SEED = 2**33 + 99


@pytest.mark.parametrize("name,mix,caught_by", [
    ("control", "closed64-fresh", "dist_rms"),
    ("altered_answer", "closed64-fresh", "dist_rms"),
    ("half_batch_left_out", "closed64-fresh", "dist_rms"),
    ("stale_state", "closed64-fresh", "dist_rms"),
    ("cache_wrong_entry", "open-zipf", "hit_mismatch"),
])
def test_fault_is_not_correct(name, mix, caught_by):
    cell = tiny.cell(mix=mix, rate=100.0, pool=64)
    fault = faults.control_for(cell.config) if name == "control" else faults.FAULTS[name]
    r = harness.run_cell(cell, seed=SEED, seconds=0.5, trace=False,
                         devices=jax.devices()[:1], t_start=0.0, fault=fault)
    assert r["correct"] is False
    c = r["checks"][caught_by]
    assert not (c["value"] <= c["limit"] if caught_by != "recall" else c["value"] >= c["limit"])


def test_the_same_cell_is_correct_unbroken():
    for mix in ("closed64-fresh", "open-zipf"):
        cell = tiny.cell(mix=mix, rate=100.0, pool=64)
        r = harness.run_cell(cell, seed=SEED, seconds=0.5, trace=False,
                             devices=jax.devices()[:1], t_start=0.0)
        assert r["correct"] is True, (mix, r["checks"])


def test_dropped_table_cuts_candidates_and_keeps_distances():
    """The recall fault: one table fewer searched, so fewer candidates,
    and every distance returned still exact (only ``recall`` can see it)."""
    cell = tiny.cell(n=16384)
    devices, mesh = harness.cell_devices(cell.config, jax.devices())
    shards, pools, _, key = make_vectors(SEED, n=16384, d=128, n_clusters=250, spread=0.02,
                                         pools={"q": 64}, devices=devices)
    Q, seen = pools["q"], {}
    for fault in (None, faults.table_dropped):
        col = harness.build(cell.config, shards, key, mesh)
        if fault is not None:
            fault(col, None, shards)
        d, ids, stats = col.search(Q, k=10, r0=1.0, steps=8, engine="inline",
                                   with_stats=True)
        err = check.dist_errors(shards, np.asarray(Q), np.asarray(ids), np.asarray(d))
        assert np.sqrt(np.mean(np.square(err))) < cell.config["correct"]["dist_rms_max"]
        seen[fault is None] = float(np.mean(stats["candidates"]))
    L = cell.config["index"]["L"]
    assert seen[False] < seen[True] * (L - 0.5) / L
