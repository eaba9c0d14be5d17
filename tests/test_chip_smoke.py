"""``chip_smoke.py`` rehearsed on CPU at a tiny size.

The script itself refuses to run without a TPU; these tests drive its
phases directly, with Pallas in interpret mode and, for the four-chip
phase, four virtual host devices in a subprocess (this process keeps its
real device count).  They guard the smoke's control flow and checks so
that a chip call is never spent finding a wrong argument.
"""

import os
import shutil
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env


@pytest.mark.parametrize("alone", [False, True])
def test_refuses_without_a_tpu(tmp_path, alone):
    """No TPU -> non-zero exit and no result line, also when the script
    sits in a directory without the rest of the repo."""
    script = os.path.join(REPO, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    proc = subprocess.run(
        [sys.executable, str(script)], env=_cpu_env(), cwd=tmp_path,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_single_chip_phase_small():
    assert jax.default_backend() == "cpu"
    chip_smoke.single_chip(n=20_000, n_queries=64, seed=0, interpret=True)


FOUR_CHIPS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, {repo!r})
import jax
import chip_smoke
chip_smoke.four_chips(n_per_chip=4096, n_queries=64, seed=0,
                      devices=jax.devices(), n_add=512, n_remove=128)
print("FOUR_CHIPS_OK")
"""


def test_four_chip_phase_small():
    proc = subprocess.run(
        [sys.executable, "-c", FOUR_CHIPS.format(repo=REPO)],
        env=_cpu_env(), capture_output=True, text=True, timeout=600,
    )
    assert "FOUR_CHIPS_OK" in proc.stdout, (
        f"stdout:\n{proc.stdout[-3000:]}\nstderr:\n{proc.stderr[-3000:]}"
    )
    assert "check ok: no removed row returned" in proc.stdout
