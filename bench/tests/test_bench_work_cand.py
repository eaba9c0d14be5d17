"""The gathered kernel's work count (``bench/work/fused_cand_search.py``),
pinned to its arithmetic written out by hand at the one-copy cell's
shapes: L = 5 tables of Ct = 20 x 64 candidates, d = 128, K = 10,
8 steps, k = 10."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

work = harness.work_module("fused_cand_search")
SHAPES = dict(L=5, Ct=1280, d=128, K=10, steps=8, k=10)


@pytest.mark.parametrize("dtype,bytes_,flops", [
    # 6,400 slots x (40 + 512 + 4 + 4) B read, 8 bins x 10 x 8 B + 8 x 4 B
    # written; 6,400 x (30 + 256 + 3) ops, and 10 rounds x 4 x (6,400 + 80)
    ("fp32", 6400 * 560 + 640 + 32, 6400 * 289 + 10 * 4 * 6480),
    # int8: 1-byte vectors and a 4-byte scale a slot; a 40-wide shortlist
    ("int8", 6400 * (40 + 128 + 8 + 4) + 8 * 40 * 8 + 32,
     6400 * 289 + 40 * 4 * (6400 + 8 * 40)),
])
def test_one_query_by_hand(dtype, bytes_, flops):
    assert work.per_query(dtype=dtype, **SHAPES) == {"bytes": bytes_, "flops": flops}


def test_a_call_is_its_queries():
    one = work.per_query(**SHAPES)
    call = work.call(32, **SHAPES)
    assert call == {"bytes": 32 * one["bytes"], "flops": 32 * one["flops"]}
    # 114.7 MB a batch of 32 at the sift10m-1chip shapes: memory-bound on
    # a v5e (least time ~0.14 ms from bytes, ~0.3 us from operations)
    assert 114e6 < call["bytes"] < 115e6
