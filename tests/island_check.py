"""Subprocess helper: verifies the sharded program (GSPMD + MoE island +
vocab-parallel CE) matches the single-device path numerically on an 8-device
host mesh — the loss and the updated parameters of one training step. Run
via tests/test_distributed.py; exits nonzero on mismatch."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

from repro.launch.mesh import make_smoke_mesh
from repro.sharding.parity import train_step_parity

LOSS_RTOL = 2e-3
UPDATE_RTOL = 1e-2


def main():
    archs = sys.argv[1:] or ["moonshot_v1_16b_a3b", "gemma3_1b",
                             "mamba2_130m", "recurrentgemma_2b",
                             "deepseek_v3_671b", "hubert_xlarge",
                             "internvl2_1b", "stablelm_1_6b"]
    mesh = make_smoke_mesh(n_data=2, n_model=2, pods=2)  # (2,2,2) = 8 devices
    worst_loss = worst_update = 0.0
    for a in archs:
        r = train_step_parity(a, mesh)
        print(f"{a}: ref={r['loss_ref']:.6f} sharded={r['loss_sharded']:.6f} "
              f"loss_rel={r['loss_rel']:.2e} update_rel={r['update_rel']:.2e}",
              flush=True)
        worst_loss = max(worst_loss, r["loss_rel"])
        worst_update = max(worst_update, r["update_rel"])
    if worst_loss > LOSS_RTOL or worst_update > UPDATE_RTOL:
        print(f"FAIL: worst loss rel err {worst_loss}, "
              f"worst update rel err {worst_update}")
        sys.exit(1)
    print(f"OK worst loss rel err {worst_loss:.2e}, "
          f"update rel err {worst_update:.2e}")


if __name__ == "__main__":
    main()
