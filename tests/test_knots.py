"""The paper's application: knot screening + knot-core localization."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.apps import knots
from repro.core import Broker, MonitorAgent, Submitter, WorkerAgent


def test_screen_separates_knots_from_coils():
    ids = list(range(32))
    coords, truth = knots.synthesize_batch(ids, n_points=128)
    wr, acn, _ = knots.writhe_and_acn(jnp.asarray(coords))
    wr = np.asarray(wr)
    deep = []
    for w, t in zip(wr, truth):
        if t in ("trefoil", "cinquefoil"):
            assert abs(w) >= knots.WRITHE_KNOT_THRESHOLD, (t, w)
        elif t == "deep_trefoil":
            deep.append(abs(w))
        else:
            assert abs(w) < knots.WRITHE_KNOT_THRESHOLD, (t, w)
    # open-chain knot detection is probabilistic (paper §4: random-closure
    # percentages); deep knots with wandering tails occasionally screen low.
    rate = np.mean([d >= knots.WRITHE_KNOT_THRESHOLD for d in deep])
    assert rate >= 0.75, (rate, deep)


def test_figure8_is_writhe_blind():
    """Documented limitation: the figure-8 knot is amphichiral (Wr ≈ 0), so a
    writhe screen cannot see it — the reason the paper's production pipeline
    computes HOMFLY-PT polynomials rather than geometric invariants."""
    f8 = knots.figure8(160)
    wr, _, _ = knots.writhe_and_acn(jnp.asarray(f8[None]))
    assert abs(float(wr[0])) < knots.WRITHE_KNOT_THRESHOLD


def test_knot_core_localizes_deep_knot():
    """For a deep knot (coil–trefoil–coil) the detected core must overlap the
    embedded trefoil and exclude most of the tails (the paper's deep/shallow
    distinction)."""
    n, core_len = 192, 96
    chain = knots.deep_knot(n, core=core_len, seed=5)
    _, _, wmap = knots.writhe_and_acn(jnp.asarray(chain[None]))
    core = knots.knot_core(np.asarray(wmap)[0])
    assert core is not None
    a, b = core
    tail = (n - core_len) // 2
    true_a, true_b = tail, tail + core_len
    overlap = max(0, min(b, true_b) - max(a, true_a))
    assert overlap > core_len * 0.7, (core, (true_a, true_b))
    assert (b - a) < n * 0.85  # tails were trimmed


def test_unknot_has_no_core():
    coil = knots.random_coil(128, seed=11)
    _, _, wmap = knots.writhe_and_acn(jnp.asarray(coil[None]))
    assert knots.knot_core(np.asarray(wmap)[0]) is None


def test_knot_campaign_end_to_end():
    """The AlphaKnot campaign in miniature: batched submission through KSA,
    load-balanced across two agents, results aggregated at the monitor."""
    broker = Broker(default_partitions=4)
    sub = Submitter(broker, "kn")
    mon = MonitorAgent(broker, "kn", poll_interval_s=0.01).start()
    a1 = WorkerAgent(broker, "kn", slots=1, poll_interval_s=0.01).start()
    a2 = WorkerAgent(broker, "kn", slots=1, poll_interval_s=0.01).start()
    try:
        ids = list(range(48))
        task_ids = sub.submit_batches("knot_batch", ids, batch_size=12,
                                      params={"n_points": 96,
                                              "stage2": True})
        assert len(task_ids) == 4
        assert mon.wait_all(task_ids, timeout=240.0)
        knotted = []
        processed = kept = 0
        for t in task_ids:
            r = mon.task(t).result
            knotted += r["knotted"]
            processed += r["processed"]
            kept += r["kept"]
        assert processed == 48
        assert kept <= 48
        # knotted population is ids % 4 in {0, 2, 3} (minus quality drops)
        assert all(i % 4 in (0, 2, 3) for i in knotted)
        assert len(knotted) >= kept * 0.4
        assert a1.tasks_completed + a2.tasks_completed == 4
    finally:
        a1.stop()
        a2.stop()
        mon.stop()
        broker.close()


def test_pipeline_screen_task_records_its_phases():
    """Each screen task's span chain holds its four phases, children of
    its run, and the campaign report sums them per stage."""
    from repro.cluster import KsaCluster
    with KsaCluster(prefix="kph", workers=1, worker_slots=1,
                    poll_interval_s=0.005) as c:
        cid = c.submit_campaign(knots.knots_pipeline(8, n_points=48),
                                list(range(8)))
        assert c.wait_campaign(cid, timeout=240.0).state == "COMPLETED"
        tid = dict(c.pipeline.stage_tasks(cid))["screen"][0]
        spans = c.trace(tid)
        rep = c.campaign_report(cid)
    kids = [s["name"] for s in spans if s.get("parent") == "run"]
    assert kids == ["knot.quality", "knot.synthesize", "knot.writhe",
                    "knot.select"]
    screen = rep["stages"]["screen"]
    assert list(screen["phases"]) == kids
    assert sum(screen["phases"].values()) <= screen["run_s"]
    assert list(rep["stages"]["localize"]["phases"]) == [
        "knot.synthesize", "knot.writhe", "knot.core"]
