"""A tiny cell for the CPU tests: the sift configurations' shapes and
service cut to a few thousand rows, two batch shapes and a one-second
window, so that the harness runs end to end in the Pallas interpreter."""

import copy
import json
from pathlib import Path

from bench import harness, traffic

ROOT = Path(__file__).resolve().parents[2]

# an open loop over a small Zipf pool, for the generator's open path and
# for cache hits: no cell of BENCHMARK.json sends such traffic yet
OPEN_ZIPF = {"loop": "open", "rate_qps": 100.0, "tenants": {"batch": 1, "web": 1},
             "queries": {"pool": 64, "order": "zipf", "zipf_s": 0.99}}


def cell(config: str = "sift1m-inline", mix: str = "closed64-fresh", *, n: int = 4096,
         chips: int = 1, clients: int = 8, rate: float = 200.0, pool: int = 512) -> harness.Cell:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json").read_text())
    cfg = copy.deepcopy(cfg)
    cfg["data"]["n"] = n
    cfg["service"]["batch_shapes"] = [1, 4]
    cfg["service"]["cache_size"] = 64
    # limits read off this size on the CPU, where the tests are
    # deterministic: sound runs 2.6e-6, the control 2.0e-5
    cfg["correct"].update(dist_rms_max=7e-6, recall_min=0.5)
    spec = (copy.deepcopy(OPEN_ZIPF) if mix == "open-zipf"
            else traffic.load(ROOT / "bench" / "traffic" / f"{mix}.json"))
    spec["clients"] = clients
    spec["rate_qps"] = rate
    spec["queries"]["pool"] = pool
    # every reader there is, so that each one runs: also those of mixes
    # and placements no cell of BENCHMARK.json uses yet
    readers = sorted(p.stem for p in (ROOT / "bench" / "layer_metrics").glob("*.py"))
    return harness.Cell(f"tiny.{mix}", chips, cfg, spec, list(bench["end_to_end"]),
                        [{"name": r, "unit": "x"} for r in readers])
