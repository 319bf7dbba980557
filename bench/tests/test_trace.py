import pytest

import tracing
from tracing import Trace


def make():
    # device busy [0,10) [20,30) [30,40) [92,100); window [0, 100)
    ops = [("%fusion.1 = f32[8] fusion(...)", 0, 10),
           ("%writhe_map.1 = f32[8,256,256] custom-call(...)", 20, 30),
           ("%writhe_map.1 = f32[9,256,256] custom-call(...)", 30, 40),
           ("%fusion.1 = f32[9] fusion(...)", 92, 100)]
    mods = [("jit_serve_step(123)", 0, 40), ("jit_serve_step(123)", 90, 100)]
    host = [("PjitFunction(serve_step)", 38, 45),
            ("ksa.screen", 10, 95), ("ksa.localize", 95, 100)]
    return Trace(lo=0, hi=100, wall_lo=1000.0, ops=[ops], modules=[mods],
                 host=host)


def test_busy_is_the_union():
    tr = make()
    assert tracing.union_ns(tr.ops[0], tr.lo, tr.hi) == 10 + 20 + 8
    assert tr.busy_s() == pytest.approx(38e-9)
    assert tr.window_s == pytest.approx(100e-9)
    assert tracing.union_ns(tr.ops[0], 5, 22) == 5 + 2


def test_gaps_and_idle_attribution():
    tr = make()
    assert tracing.gaps(tr.ops[0], tr.lo, tr.hi) == [(10, 20), (40, 92)]
    # (10,20) mid 15: only ksa.screen open; (40,92) mid 66: ksa.screen
    assert tracing.idle_by_host(tr) == [["ksa.screen", pytest.approx(62e-9)]]
    assert tracing.host_activity(tr.host, 40) == "PjitFunction(serve_step)"
    assert tracing.host_activity(tr.host, 200) == "none"


def test_kernel_time_by_stable_name():
    tr = make()
    ev = tracing.matching(tr.ops[0], "writhe_map", tr.lo, tr.hi)
    assert len(ev) == 2 and tracing.total_s(ev) == pytest.approx(20e-9)
    # the HLO text's shapes are not part of the name
    assert tracing.matching(tr.ops[0], "f32[8", 0, 100) == []
    steps = tracing.matching(tr.modules[0], "jit_serve_step", 0, 100)
    assert tracing.total_s(steps) == pytest.approx(50e-9)


def test_top_ops_group_an_instruction_across_shapes():
    top = tracing.top_ops(make())
    assert top[0] == ["%writhe_map.1", pytest.approx(20e-9)]
    assert top[1] == ["%fusion.1", pytest.approx(18e-9)]
    b = tracing.breakdown(make())
    assert set(b) == {"device_ops", "idle_gaps"}


def test_self_time_takes_nested_ops_out_of_their_parent():
    ev = [("%while.5 = (...) while(...)", 0, 100),
          ("%fusion.2 = f32[1] fusion(...)", 10, 30),
          ("%flash_decode_paged.9 = bf16[1] custom-call(...)", 40, 90),
          ("%copy.1 = f32[1] copy(...)", 120, 130)]
    st = dict(tracing.self_times(ev))
    assert st[ev[0][0]] == 100 - 20 - 50
    assert st[ev[1][0]] == 20 and st[ev[2][0]] == 50 and st[ev[3][0]] == 10
    tr = Trace(lo=0, hi=200, wall_lo=0.0, ops=[ev])
    assert tracing.top_ops(tr)[0] == ["%flash_decode_paged.9",
                                      pytest.approx(50e-9)]


def test_wall_spans_land_on_the_trace_clock():
    tr = make()
    tr.add_wall_spans([("ksa.aggregate", 1000.0 + 50e-9, 1000.0 + 60e-9)])
    assert ("ksa.aggregate", 50, 60) in tr.host


def test_capture_and_load_on_this_host(tmp_path):
    """A real profiler session: the markers are found and the window has
    their length (no device plane off the chip, so no device ops)."""
    import time

    import jax.numpy as jnp

    cap = tracing.Capture(tmp_path)
    cap.start()
    (jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready()
    time.sleep(0.05)
    cap.stop()
    tr = cap.load()
    assert tr.window_s == pytest.approx(cap.wall_close - cap.wall_open,
                                        abs=0.02)
    assert tr.hi > tr.lo and tr.host
