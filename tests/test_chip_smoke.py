"""chip_smoke.py off the chip: the script refuses to run without a TPU, and
each of its phases passes at smoke size on the CPU (jnp reference writhe,
interpret-mode flash-decode), so a broken phase shows here before it costs
a chip run."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.configs import smoke_config  # noqa: E402


def _bf16_smoke():
    return smoke_config("stablelm_1_6b").with_(dtype="bfloat16")


def _env(**extra):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.update(extra)
    return env


def test_refuses_to_run_without_tpu():
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300,
                       env=_env(), cwd=str(ROOT))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_knot_phase_small():
    out = chip_smoke.knot_phase(n_structures=120, batch_size=48,
                                n_points=64, ref_subset=16)
    assert out["structures"] == 120 and out["batches"] == 3
    assert out["knotted"] > 0 and out["ref_checked"] > 0


def test_serve_phase_small():
    out = chip_smoke.serve_phase(_bf16_smoke(), n_slots=4, max_len=64,
                                 prompt_lens=(3, 17, 5, 30, 9, 12),
                                 max_new=6, interpret=True)
    assert out["requests"] == 6 and out["tokens"] == 36
    assert out["worst_logit_rel"] <= chip_smoke.LOGIT_RTOL


@pytest.mark.parametrize("phase", ["sharded", "replicas"])
def test_multichip_phases_on_virtual_devices(phase):
    """The ``--chips 4`` phases on four virtual CPU devices (a subprocess:
    the device count is fixed when JAX starts)."""
    call = {"sharded": "chip_smoke.sharded_phase()",
            "replicas": ("chip_smoke.replica_phase(cfg, n_slots=4, "
                         "max_len=64, prompt_lens=tuple(3 + 2 * i for i in "
                         "range(12)), max_new=6, interpret=True)")}[phase]
    code = ("import json, sys; sys.path.insert(0, '.'); import chip_smoke; "
            "from repro.configs import smoke_config; "
            "cfg = smoke_config('stablelm_1_6b').with_(dtype='bfloat16'); "
            f"print(json.dumps({call}))")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, cwd=str(ROOT),
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    if phase == "sharded":
        assert [o["arch"] for o in out] == list(chip_smoke.SHARDED_ARCHS)
    else:
        assert out["replicas"] == 4 and out["tokens"] == 72
