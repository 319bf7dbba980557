"""Readings that set a cell's limits: the program's numbers over many seeds
and the control's on the same inputs, in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 20

For each seed it runs the cell as ``run.py`` does (a short window at the
cell's own load) and then the configuration's control: the plain reference
computed one precision below the configuration's, put in the program's
place and compared in the same way, against the cell's own limits. One
JSON line per seed goes to standard output, with ``correct`` for the
program and ``control_correct`` for the control, which has to be false.
It exits 1 if the control came out correct on any seed. The benchmark's
own runs never run the control.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    import harness
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        harness.log("control: no TPU; nothing was run")
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    spec = harness.load_spec(ROOT)
    counter = harness.CompileCounter().register()
    passed = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        run, driver = harness.prepare(spec, args.workload, seed=seed,
                                      seconds=args.seconds, trace=False,
                                      t_start=t0)
        run.compiles = counter
        driver.run(run)
        program = {k: v for k, (v, _) in run.checks.items()}
        control = driver.control_checks(run)
        control_correct = run.correct_with(control)
        if control_correct:
            passed.append(seed)
        print(json.dumps({"seed": seed, "correct": run.correct,
                          "control_correct": control_correct,
                          "program": program, "control": control,
                          "limits": {k: lim for k, (_, lim)
                                     in run.checks.items()},
                          "e2e": run.e2e}), flush=True)
        del run
    if passed:
        harness.log(f"control: correct on seeds {passed}; it must fail")
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
