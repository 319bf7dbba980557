"""The readers of the program's phase spans, on synthetic readings, and
the phases themselves on a real profiler trace of this host: the knot
screen's and the serve step loop's spans land on the host plane, nested
in what encloses them."""
import threading

import pytest

import harness
import tracing
from tracing import Trace

KNOT = ("quality", "synthesize", "writhe")
SERVE = ("loop", "assemble", "dispatch", "sync", "apply")


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                               "test_metric_" + name.replace(".", "_"))


def report(tasks, phases):
    return {"stages": {"screen": {"tasks": tasks, "run_s": 9.0,
                                  **({"phases": phases} if phases is not None
                                     else {})}}}


@pytest.mark.parametrize("phase", KNOT)
def test_knot_phase_per_batch(phase):
    read = reader(f"knot.{phase}_ms_per_batch").read
    name = f"knot.{phase}"
    reps = [report(4, {name: 0.5, "knot.other": 7.0}),
            report(4, {name: 0.3})]
    assert read({"campaign_reports": reps}) == pytest.approx(100.0)
    # a campaign whose screen never reached the phase still counts its
    # batches, as knot.screen_ms_per_batch does
    reps.append(report(2, {}))
    assert read({"campaign_reports": reps}) == pytest.approx(80.0)
    # a program without phases, or nothing finished: nothing to read
    assert read({"campaign_reports": [report(4, None)]}) is None
    assert read({"campaign_reports": []}) is None
    assert read({}) is None


@pytest.mark.parametrize("phase", SERVE)
def test_serve_phase_mean_on_the_trace(phase):
    read = reader(f"serve.{phase}_ms.batch").read
    name = f"serve.{phase}"
    host = [(name, 1_000_000, 3_000_000), (name, 5_000_000, 9_000_000),
            (name, -1_000_000, 500_000),          # opens before lo
            (name, 99_000_000, 101_000_000),      # closes after hi
            ("serve.other", 10_000_000, 90_000_000)]
    tr = Trace(lo=0, hi=100_000_000, wall_lo=0.0, host=host)
    assert read({"trace": tr}) == pytest.approx(3.0)
    assert read({"trace": Trace(lo=0, hi=10, wall_lo=0.0)}) is None
    assert read({}) is None


def nested(events, parent, child):
    """Every ``child`` event lies inside some ``parent`` event."""
    outer = [(a, b) for n, a, b in events if n == parent]
    inner = [(a, b) for n, a, b in events if n == child]
    return bool(inner) and all(any(pa <= a and b <= pb for pa, pb in outer)
                               for a, b in inner)


def test_phases_land_on_the_host_plane(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.apps import knots
    from repro.configs import smoke_config
    from repro.models import init_params, model_spec
    from repro.serve import ServeReplicaSet

    cfg = smoke_config("stablelm_1_6b")
    params = init_params(model_spec(cfg), jax.random.PRNGKey(0),
                         jnp.dtype(cfg.dtype))
    rs = ServeReplicaSet(cfg, params, n_replicas=1,
                         engine_kw=dict(n_slots=2, max_len=64, paged=True,
                                        page_size=16))
    rs.submit("warm", [1, 2, 3], max_new=2)
    while rs._drive_once(0):
        pass
    ids = list(range(8))
    knots._screen_batch(ids, 64)

    def screen():
        with jax.profiler.TraceAnnotation("task"):
            knots._screen_batch(ids, 64)

    cap = tracing.Capture(tmp_path)
    cap.start()
    t = threading.Thread(target=screen)
    t.start()
    t.join(60)
    rs.submit("r0", [4, 5, 6], max_new=2)
    while rs._drive_once(0):
        pass
    cap.stop()
    assert not t.is_alive()
    host = cap.load().host
    names = {n for n, _, _ in host}
    for p in ("quality", "synthesize", "writhe", "select"):
        assert nested(host, "task", f"knot.{p}"), p
    for p in ("admit", "assemble", "dispatch", "sync", "apply"):
        assert nested(host, "serve.loop", f"serve.{p}"), p
    assert {"knot.quality", "serve.loop"} <= names
