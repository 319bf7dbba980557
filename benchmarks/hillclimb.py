"""§Perf hillclimbing driver: compiles tagged optimization variants of the
three chosen cells and prints before/after roofline terms.

Cells (selection rationale in EXPERIMENTS.md §Perf):
  1. deepseek-v3-671b × decode_32k   — most collective-bound cell
  2. deepseek-v3-671b × train_4k     — worst roofline fraction / HBM violator
  3. moonlight-16b-a3b × train_4k  — most representative of the EP (expert-
                                        parallel) substrate of this system

Run:  PYTHONPATH=src python -m benchmarks.hillclimb [--cell N]
"""
import sys

from repro.launch.dryrun import run_cell  # sets XLA_FLAGS first

from pathlib import Path

OUT = Path("results/dryrun")

VARIANTS = {
    "deepseek_decode": [
        ("deepseek_v3_671b", "decode_32k", {}, ""),
        ("deepseek_v3_671b", "decode_32k",
         {"dist_flags": ["flash_decode"]}, "flashdec"),
        ("deepseek_v3_671b", "decode_32k",
         {"dist_flags": ["flash_decode", "weight_stationary"]}, "flashdec_ws"),
    ],
    "deepseek_train": [
        ("deepseek_v3_671b", "train_4k", {}, ""),
        ("deepseek_v3_671b", "train_4k",
         {"dist_flags": ["fp8_gather"]}, "fp8"),
        ("deepseek_v3_671b", "train_4k",
         {"dist_flags": ["fp8_gather", "chunked_ce"]}, "fp8_cce"),
        ("deepseek_v3_671b", "train_4k",
         {"dist_flags": ["fp8_gather", "chunked_ce"], "microbatch": 8},
         "fp8_cce_mu8"),
        ("deepseek_v3_671b", "train_4k",
         {"dist_flags": ["fp8_gather", "chunked_ce"],
          "score_dtype": "bfloat16"}, "fp8_cce_bf16s"),
    ],
    "moonlight_train": [
        ("moonlight_16b_a3b", "train_4k", {}, ""),
        ("moonlight_16b_a3b", "train_4k",
         {"dist_flags": ["chunked_ce"]}, "cce"),
        ("moonlight_16b_a3b", "train_4k",
         {"dist_flags": ["chunked_ce", "fp8_gather"]}, "cce_fp8"),
        ("moonlight_16b_a3b", "train_4k",
         {"dist_flags": ["chunked_ce", "fp8_gather"], "microbatch": 4},
         "cce_fp8_mu4"),
        ("moonlight_16b_a3b", "train_4k",
         {"dist_flags": ["chunked_ce", "fp8_gather"],
          "score_dtype": "bfloat16"}, "cce_fp8_bf16s"),
    ],
}


def main() -> None:
    which = sys.argv[1:] or list(VARIANTS)
    for group in which:
        print(f"\n=== {group} ===", flush=True)
        for arch, shape, overrides, tag in VARIANTS[group]:
            rec = run_cell(arch, shape, False, OUT, overrides=overrides,
                           tag=tag)
            r = rec.get("roofline", {})
            m = rec.get("memory", {})
            print(f"  [{tag or 'baseline':>12}] "
                  f"compute={r.get('compute_s', 0):8.4f}s "
                  f"mem={r.get('memory_s', 0):8.4f}s "
                  f"coll={r.get('collective_s', 0):8.4f}s "
                  f"dom={r.get('dominant', '?'):10} "
                  f"mfu={r.get('mfu_bound', 0):.4f} "
                  f"hbm={(m.get('per_device_total_bytes') or 0)/1e9:6.1f}GB",
                  flush=True)


if __name__ == "__main__":
    main()
