"""Distributed-path correctness: the sharded program (GSPMD + shard_map
islands) must match the single-device program. Runs in a subprocess because
the host-device-count flag must be set before jax initializes."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


def _run(args, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=timeout, env=env,
                          cwd=str(ROOT))


@pytest.mark.slow
def test_sharded_matches_single_device_all_families():
    r = _run([str(ROOT / "tests" / "island_check.py")])
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]


def test_sharded_matches_single_device_moe():
    r = _run([str(ROOT / "tests" / "island_check.py"),
              "moonlight_16b_a3b"])
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]


def test_dryrun_smoke_cell(tmp_path):
    """One real dry-run cell end-to-end (small arch) on the production mesh
    machinery — exercises dryrun.py exactly as the full matrix does."""
    r = _run(["-m", "repro.launch.dryrun", "--arch", "mamba2_130m",
              "--shape", "train_4k", "--single-pod",
              "--out", str(tmp_path), "--force"], timeout=1800)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "OK" in r.stdout
