"""The fused kernel's work count: the arithmetic of the fused half of
``benchmarks/roofline.py:search_cell``, from which it was copied."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

work = harness.work_module("fused_window_search")


@pytest.mark.parametrize("dtype", ["fp32", "bf16", "int8"])
def test_pinned_to_search_cell(dtype):
    from benchmarks.roofline import search_cell

    ref = search_cell(S=25, B=64, d=128, K=10, steps=8, k=10, dtype=dtype)["fused"]
    got = work.per_query(S=25, B=64, d=128, K=10, steps=8, k=10, dtype=dtype)
    assert got["bytes"] == ref["bytes_per_query"]
    assert got["flops"] == ref["flops_per_query"]


def test_a_call_is_its_queries():
    one = work.per_query(S=25, B=64, d=128, K=10, steps=8, k=10)
    call = work.call(32, L=5, M=5, B=64, d=128, K=10, steps=8, k=10)
    assert call == {"bytes": 32 * one["bytes"], "flops": 32 * one["flops"]}
    # 28.7 MB a batch of 32 at the sift1m shapes
    assert 28e6 < call["bytes"] < 29e6
