"""The harness's phases end to end at a tiny size on the CPU, with the
Pallas kernel in the interpreter; and its refusal to run without a TPU."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench.tests import tiny  # noqa: E402

SEED = 2**33 + 7


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env


@pytest.mark.parametrize("alone", [False, True])
def test_refuses_without_a_tpu(tmp_path, alone):
    """No TPU: a non-zero exit and no result line, in the repo and in a
    directory that holds only BENCHMARK.json and bench/."""
    root = ROOT
    if alone:
        root = tmp_path / "checkout"
        shutil.copytree(ROOT / "bench", root / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sift1m.closed64", "--seed", str(SEED),
         "--seconds", "1", "--trace", "0"],
        env=_cpu_env(), cwd=root, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def _keys_in_order(result):
    return list(result)


def test_closed_loop_cell_end_to_end():
    assert jax.default_backend() == "cpu"
    cell = tiny.cell()
    r = harness.run_cell(cell, seed=SEED, seconds=0.5, trace=False,
                         devices=jax.devices()[:1], t_start=0.0)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    m = r["metrics"]
    # the CPU may complete nothing inside so short a window when the test
    # shares it; every request is still answered and timed after it
    assert m["qps"]["value"] >= 0 and m["p99_ms"]["value"] > 0
    assert m["recall_at_10"]["value"] > 0.5 and m["setup_s"]["value"] > 0
    assert _keys_in_order(r)[-1] == "checks"
    assert set(r["checks"]) == {"dist_rms", "recall", "hit_mismatch"}
    json.dumps(r)  # the result line is plain JSON


def test_open_loop_cell_traced():
    """The trace run reports the per-layer metrics it finds something for:
    the program's spans and counters here; the CPU has no device plane, so
    the device metrics are left out, never reported as 0."""
    cell = tiny.cell(mix="open-zipf", rate=100.0, pool=64)
    r = harness.run_cell(cell, seed=SEED, seconds=0.5, trace=True,
                         devices=jax.devices()[:1], t_start=0.0)
    assert r["correct"] is True, r["checks"]
    m = r["metrics"]
    assert m["host_ms_per_batch.closed"]["value"] > 0
    for device_metric in ("select_ms_per_batch", "verify_ms_per_batch",
                          "fused_window_search_roofline", "device_idle_share.closed",
                          "collective_ms_per_batch"):
        assert device_metric not in m
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "busy_s" in r["device"] and "window_s" in r["device"]
