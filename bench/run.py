"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are declared in
``BENCHMARK.json`` at the root of the checkout; ``bench/harness.py`` says
where their files live. With ``--trace 0`` the result line carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics and a
``breakdown`` of the profiler trace. The numbers that decide ``correct``
are printed, each beside its limit, as the last lines of standard error
and under ``checks``, the last key of the result line.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    spec = harness.load_spec(ROOT)
    cell = harness.find(spec["workloads"], args.workload, "workload")
    if not (ROOT / "src" / "repro").is_dir():
        harness.log("bench: the program (src/repro) is not in this "
                    "checkout; nothing was run")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    bound = harness.bind_host_cpus(
        harness.config_of(spec, args.workload).get("host_cpus"))

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        harness.log(f"bench: no TPU (JAX found {devices[0].platform}); "
                    "nothing was run")
        return 2
    if len(devices) < int(cell["chips"]):
        harness.log(f"bench: {args.workload} needs {cell['chips']} chips, "
                    f"JAX found {len(devices)}; nothing was run")
        return 2
    devices = devices[:int(cell["chips"])]

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    harness.log(f"bench: {args.workload} seed {args.seed} on "
                f"{devices[0].device_kind} x{len(devices)}, compile cache "
                f"{cache}, host cpus {bound or 'unbound'}")
    run, driver = harness.prepare(spec, args.workload, seed=args.seed,
                                  seconds=args.seconds,
                                  trace=bool(args.trace), t_start=T_START)
    shutil.rmtree(run.out_dir, ignore_errors=True)
    run.compiles = harness.CompileCounter().register()
    driver.run(run)
    line = harness.result_line(run, spec, devices)
    for name, (value, limit) in run.checks.items():
        harness.log(f"check {name} {value!r} limit {limit!r}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
