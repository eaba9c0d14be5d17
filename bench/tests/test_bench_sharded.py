"""The sharded placement's path at a tiny size on four virtual CPU
devices, in a subprocess (this process keeps its own device count),
meshed in the order a v5e 2x2 is: sound, it is correct; with the
exchange between chips left out, or with the control in the program's
place, it is not; with a hash table dropped, its distances stay exact."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

SCRIPT = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {root!r})
sys.path.insert(0, {src!r})
import jax
import numpy as np
from jax.sharding import AxisType, Mesh
import repro.compat
from bench import faults, harness
from bench.tests import tiny

# a v5e 2x2 meshes its chips as [0, 1, 3, 2]: so does this one, so that a
# harness that numbers rows in jax.devices() order reads wrong answers
def make_mesh(shape, axes, *, devices=None):
    return Mesh(np.array(devices)[[0, 1, 3, 2]].reshape(shape), axes,
                axis_types=(AxisType.Auto,) * len(axes))
repro.compat.make_mesh = make_mesh
cell = tiny.cell(config="sift10m-sharded4", n=4 * 1024, chips=4)
out = {{}}
for name, fault in (("sound", None), ("control", faults.control_for(cell.config)),
                    ("table_dropped", faults.table_dropped),
                    ("exchange_left_out", faults.exchange_left_out)):
    r = harness.run_cell(cell, seed=2**33 + 3, seconds=0.5, trace=False,
                         devices=jax.devices()[:4], t_start=0.0, fault=fault)
    out[name] = {{"correct": r["correct"], "checks": r["checks"]}}
print("RESULT " + json.dumps(out))
"""


def test_sharded_cell_sound_and_broken():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(root=str(ROOT), src=str(ROOT / "src"))],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [x for x in proc.stdout.splitlines() if x.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out["sound"]["correct"] is True, out["sound"]
    # a table fewer leaves every distance exact, on the sharded index too
    dropped = out["table_dropped"]["checks"]["dist_rms"]
    assert dropped["value"] <= dropped["limit"]
    gone = out["exchange_left_out"]
    assert gone["correct"] is False
    assert gone["checks"]["recall"]["value"] < gone["checks"]["recall"]["limit"]
    ctl = out["control"]
    assert ctl["correct"] is False
    assert ctl["checks"]["dist_rms"]["value"] > ctl["checks"]["dist_rms"]["limit"]
