"""The harness's binding of a run to host cores, read from the cell's
configuration before JAX or the cluster starts a thread."""
import os
import subprocess
import sys

import harness


def test_every_cell_has_a_configuration_with_its_driver():
    spec = harness.load_spec()
    for w in spec["workloads"]:
        cfg = harness.config_of(spec, w["name"])
        assert (harness.BENCH / "drivers" / f"{cfg['driver']}.py").exists()
        n = cfg.get("host_cpus")
        assert n is None or (isinstance(n, int) and n >= 1)


def test_no_binding_leaves_the_process_as_it_was():
    before = os.sched_getaffinity(0)
    assert harness.bind_host_cpus(None) is None
    assert os.sched_getaffinity(0) == before


def test_binding_holds_for_threads_started_after_it():
    code = (
        "import os, sys, threading; sys.path.insert(0, sys.argv[1]);"
        "import harness; cpus = harness.bind_host_cpus(1); seen = [];"
        "t = threading.Thread(target=lambda: seen.append("
        "sorted(os.sched_getaffinity(0)))); t.start(); t.join();"
        "assert seen == [cpus] and len(cpus) == 1, (seen, cpus);"
        "assert cpus == sorted(os.sched_getaffinity(0))[:1]")
    subprocess.run([sys.executable, "-c", code, str(harness.BENCH)],
                   check=True, timeout=60)
