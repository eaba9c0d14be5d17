#!/usr/bin/env python3
"""The readings a cell's limits are set from, on the chip: the numbers
``check.py`` compares, for many seeds in one process, of the program as
the benchmark runs it (``program``), of the control (``control``: the
reference one precision below in the program's place) or of a planted
fault (a name in ``faults.FAULTS``).

    python3 bench/readings.py --workload sift1m.closed64 \\
        --mode program table_dropped --seconds 3 --seeds 11 12 13 ...

One JSON line a seed and mode on standard output.  The benchmark's own runs never
run this.
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import faults, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", nargs="+", required=True,
                    choices=("program", "control", *faults.FAULTS))
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.resolve(json.loads((harness.ROOT / "BENCHMARK.json").read_text()),
                           args.workload)
    devices = harness.chip_devices(cell)
    if devices is None:
        return 2
    for seed in args.seeds:
        for mode in args.mode:
            fault = (None if mode == "program" else
                     faults.control_for(cell.config) if mode == "control"
                     else faults.FAULTS[mode])
            t0 = time.perf_counter()
            r = harness.run_cell(cell, seed=seed, seconds=args.seconds, trace=False,
                                 devices=devices, t_start=t0, interpret=False, fault=fault)
            print(json.dumps({"workload": args.workload, "mode": mode, "seed": seed,
                              "correct": r["correct"], "checks": r["checks"],
                              "attempted": r["attempted"], "failed": r["failed"],
                              "metrics": r["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
