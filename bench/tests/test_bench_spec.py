"""BENCHMARK.json and the files it names: every cell resolves, the file
keeps to its format, and a cell is added by new files and one entry."""

import json
import re
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, traffic  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_names_units_and_entries():
    seen = set()
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[kind]:
            assert NAME.match(e["name"]), e["name"]
            assert e["name"] not in seen
            seen.add(e["name"])
    metric_keys = {"name", "unit", "better", "source"}
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == metric_keys | {"bound"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == metric_keys | {"layer", "moves"}
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert len({(w["config"], w["traffic"]) for w in BENCH["workloads"]}) == len(CELLS)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(CELLS) // 2)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves(cell):
    c = harness.resolve(BENCH, cell)
    assert c.chips in (1, 4)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"} and len(c.end_to_end) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        reader = harness.load_module(ROOT / "bench" / "layer_metrics" / f"{m['name']}.py")
        assert callable(reader.read)
    cfg = c.config
    assert cfg["correct"]["recall_min"] > 0
    assert cfg["data"]["n"] % c.chips == 0
    assert cfg["service"]["batch_shapes"] == sorted(cfg["service"]["batch_shapes"])


def test_a_cell_is_added_by_new_files_and_one_entry(tmp_path):
    """A new traffic mix, configuration and per-layer metric are
    found by name without an edit to any file the benchmark has."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    mix = json.loads((ROOT / "bench" / "traffic" / "closed64-fresh.json").read_text())
    mix["clients"] = 8
    (tmp_path / "bench" / "traffic" / "closed8-fresh.json").write_text(json.dumps(mix))
    cfg = json.loads((ROOT / "bench" / "configs" / "sift1m-inline.json").read_text())
    cfg["data"]["n"] = 100_000
    (tmp_path / "bench" / "configs" / "sift100k-inline.json").write_text(json.dumps(cfg))
    (tmp_path / "bench" / "layer_metrics" / "batch_fill.py").write_text(
        "def read(ctx):\n    return None\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "sift100k-inline", "source": "https://example.org",
                             "file": "bench/configs/sift100k-inline.json", "reduced": ["n"],
                             "why": "smaller"})
    bench["workloads"].append({"name": "sift100k.closed8", "config": "sift100k-inline",
                               "traffic": "closed8-fresh", "chips": 1, "why": "new"})
    bench["per_layer"].append({"name": "batch_fill", "unit": "fraction", "better": "higher",
                               "source": "program_span", "layer": "admission", "moves": "qps",
                               "workloads": ["sift100k.closed8"]})
    c = harness.resolve(bench, "sift100k.closed8", root=tmp_path)
    assert c.config["data"]["n"] == 100_000 and c.traffic["clients"] == 8
    assert [m["name"] for m in c.per_layer if m["name"] == "batch_fill"] == ["batch_fill"]
    assert all(p.read_bytes() == b for p, b in before.items())
    old = harness.resolve(bench, CELLS[0], root=tmp_path)
    assert "batch_fill" not in {m["name"] for m in old.per_layer}


def test_a_missing_reader_is_an_error():
    bench = json.loads(json.dumps(BENCH))
    bench["per_layer"].append({"name": "no_such_metric", "unit": "ms", "better": "lower",
                               "source": "device_trace", "layer": "device", "moves": "qps"})
    with pytest.raises(FileNotFoundError):
        harness.resolve(bench, CELLS[0])


def test_traffic_files_load():
    for w in BENCH["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    for path in (ROOT / "bench" / "traffic").glob("*.json"):
        assert traffic.load(path)["tenants"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v99")
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
