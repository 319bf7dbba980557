"""The knot cells' comparison, driven end to end on this host at a small
size (the harness's look for a chip skipped): a sound run is correct, the
bfloat16 control fails, and an answer altered where the program produces
it makes ``correct`` false."""
import time

import numpy as np
import pytest

import harness

CFG = {"batch_size": 64, "n_points": 48}
MIX = {"knotted_share": 0.5, "plan_batches": 2, "batches_per_campaign": 2,
       "setup_timeout_s": 300}


def drive(cell, seed, tmp_path):
    spec = harness.load_spec()
    run, driver = harness.prepare(spec, cell, seed=seed, seconds=1.5,
                                  trace=False, t_start=time.time(),
                                  config_override=CFG, mix_override=MIX)
    run.out_dir = tmp_path
    run.compiles = harness.CompileCounter()
    driver.run(run)
    return run, driver


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return drive("knot.afdb_screen", 3, tmp_path_factory.mktemp("sound"))


def test_sound_run_is_correct(sound):
    run, _ = sound
    assert run.correct, run.checks
    assert run.readings["structures_per_s"] > 0 and run.attempted > 0
    assert run.checks["wr_gap"][0] < run.checks["wr_gap"][1]


def test_control_fails(sound):
    run, driver = sound
    ctl = driver.control_checks(run)
    assert not run.correct_with(ctl), ctl
    assert ctl["wr_gap"] >= 3 * run.checks["wr_gap"][0]


def test_writhe_altered_at_the_screen(tmp_path, monkeypatch):
    from repro.apps import knots

    real = knots.writhe_and_acn

    def altered(coords):
        wr, acn, w = real(coords)
        return wr + 0.05, acn, w

    monkeypatch.setattr(knots, "writhe_and_acn", altered)
    run, _ = drive("knot.afdb_screen", 4, tmp_path)
    assert not run.correct
    assert run.checks["wr_gap"][0] > run.checks["wr_gap"][1]


def test_core_altered_at_localize(tmp_path, monkeypatch):
    from repro.apps import knots

    real = knots.knot_core

    def altered(wmap, *a, **kw):
        core = real(wmap, *a, **kw)
        return None if core is None else (core[0] + 1, core[1])

    monkeypatch.setattr(knots, "knot_core", altered)
    run, _ = drive("knot.afdb_screen", 5, tmp_path)
    assert not run.correct
    assert run.checks["core_errors"][0] > 0


def test_reference_core_margin_marks_near_ties():
    ref = harness.load_module(harness.BENCH / "configs"
                              / "alphaknot_screen.py", "ref_knot")
    w = np.zeros((20, 20))
    w[5, 6] = w[6, 5] = 2.5   # the subchain writhe sits on the threshold
    core, margin = ref.knot_core(w, 2.5, 2)
    assert core == (5, 7) and margin == 0.0
    assert ref.knot_core(np.zeros((8, 8)), 2.5, 2)[0] is None


def test_chip_time_counts_whole_tasks_inside_the_window():
    """Device time of the screen and localize runs wholly in the window,
    per 1000 structures those screens took in; a task that straddles an
    edge, and device time outside any task, are left out."""
    from tracing import Trace

    driver = harness.load_module(harness.BENCH / "drivers"
                                 / "knot_campaign.py", "knot_driver_t")
    s = 1e-9   # one trace ns in wall seconds (trace clock = wall clock)
    ops = [("%writhe_map.1", 12, 22), ("%reduce.2", 24, 25),
           ("%writhe_map.1", 31, 33), ("%writhe_map.1", 41, 51),
           ("%fusion.3", 60, 70), ("%writhe_map.1", 5, 9)]
    tr = Trace(lo=0, hi=100, wall_lo=0.0, ops=[ops])
    spans = [("screen", 3 * s, 10 * s, 2000),     # starts before the window
             ("screen", 11 * s, 30 * s, 2000),
             ("localize", 30 * s, 35 * s, 20),
             ("screen", 40 * s, 55 * s, 1990),
             ("aggregate", 58 * s, 72 * s, None),
             ("localize", 75 * s, 99 * s, 20)]     # ends after the close
    got = driver.chip_ms_per_kstructure(tr, spans, 10 * s, 80 * s, 4000)
    # (10 + 1 + 2 + 10) ns over two screens of 4000 structures
    assert got == pytest.approx(23e-6 / 8)
    assert driver.chip_ms_per_kstructure(
        Trace(lo=0, hi=100, wall_lo=0.0, ops=[[]]), spans, 10 * s, 80 * s,
        4000) is None
    assert driver.chip_ms_per_kstructure(tr, spans[:1], 10 * s, 80 * s,
                                         4000) is None


def test_throughput_is_read_per_layer(sound):
    run, _ = sound
    reader = harness.load_module(harness.BENCH / "metrics"
                                 / "knot.structures_per_s.py", "knot_sps")
    assert reader.read(run.readings) == run.readings["structures_per_s"]
    assert reader.read({}) is None
