"""The one-copy configuration (``sift10m-1chip``) end to end on the CPU:
the tiny cell of its shapes is ``correct``, and the comparison fails
the control and the dropped table on it."""

import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import faults, harness  # noqa: E402
from bench.tests import tiny  # noqa: E402

SEED = 2**33 + 161


def run(cell, fault=None):
    return harness.run_cell(cell, seed=SEED, seconds=0.5, trace=False,
                            devices=jax.devices()[:1], t_start=0.0, fault=fault)


def test_onecopy_cell_is_correct():
    cell = tiny.cell("sift10m-1chip")
    assert cell.config["service"]["engine"] == "kernel"
    assert cell.config["index"]["inline_vectors"] is False
    r = run(cell)
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0


def test_control_is_not_correct():
    cell = tiny.cell("sift10m-1chip")
    r = run(cell, faults.control_for(cell.config))
    assert r["correct"] is False
    assert r["checks"]["dist_rms"]["value"] > r["checks"]["dist_rms"]["limit"]


@pytest.mark.parametrize("fault", [None, "table_dropped"])
def test_dropped_table_is_not_correct(fault):
    """At a few thousand rows M = 20 blocks a table verify nearly every
    row, and recall cannot see a table fewer: one block a table makes a
    table's share of the candidates show.  Read on the CPU at this size
    over five seeds: sound 0.771-0.796, the table dropped 0.700-0.717."""
    cell = tiny.cell("sift10m-1chip", n=16384)
    cell.config["index"]["max_blocks"] = 1
    cell.config["correct"]["recall_min"] = 0.745
    r = run(cell, fault and faults.FAULTS[fault])
    assert r["correct"] is (fault is None), r["checks"]
    assert r["checks"]["dist_rms"]["value"] <= r["checks"]["dist_rms"]["limit"]
