"""Observability layer (repro.obs): the metrics registry (counters, gauges,
per-class latency histograms with exact quantiles, Prometheus text render),
the bounded span store with linked per-attempt chains (submit → grant →
claim → run → commit / revoke), the monitor's /metrics and /trace/<id>
endpoints, KsaCluster.trace / campaign_report, real-RSS mem policing, and
the schema-stability guarantees for the legacy stats()/status()/summary()
views that now read through the registry."""
import json
import re
import time
import urllib.error
import urllib.request

import pytest

from repro.cluster import KsaCluster
from repro.core import (Broker, ClusterComputing, Consumer, FairShare,
                        RevokeReason, Submitter, register_script)
from repro.core.messages import topic_names
from repro.core.monitor import ROUTES
from repro.obs import (DEFAULT_BUCKETS, AlertEngine, AlertRule,
                       FlightRecorder, MetricsRegistry, NullSpanStore,
                       SloSpec, SpanStore, TelemetryCollector,
                       TimeSeriesStore, merge_renders, phase,
                       sample_rss_mb, task_scope, topic_class)
from repro.pipeline import PipelineAgent, PipelineSpec, RetryPolicy, Stage


def _wait(cond, timeout=10.0, interval=0.01):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}") as r:
        body = r.read()
        ctype = r.headers.get("Content-Type", "")
    return body, ctype


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------

def test_registry_counters_gauges_and_label_interning():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "help", labels=("event",))
    c.labels(event="a").inc()
    c.labels(event="a").inc(2)
    c.labels(event="b").inc()
    assert c.labels(event="a").value == 3
    assert c.labels(event="b").value == 1
    assert c.labels(event="a") is c.labels(event="a")  # interned child
    g = reg.gauge("t_gauge")
    g.set(5)
    g.dec(2)
    assert g.value == 3.0
    # registering the same name again returns the same family; a type or
    # label mismatch is a programming error
    assert reg.counter("t_total", labels=("event",)) is c
    with pytest.raises(ValueError):
        reg.gauge("t_total")
    with pytest.raises(ValueError):
        reg.counter("t_total", labels=("other",))
    with pytest.raises(ValueError):
        c.labels(wrong="a")


def test_histogram_buckets_and_exact_quantiles():
    reg = MetricsRegistry()
    h = reg.histogram("t_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count == 5
    assert h.sum == pytest.approx(56.05)
    snap = h._default().snapshot()
    # cumulative buckets: le=0.1 -> 1, le=1.0 -> 3, le=10.0 -> 4, +Inf -> 5
    assert snap["buckets"] == {0.1: 1, 1.0: 3, 10.0: 4}
    assert snap["inf"] == 5
    assert h.quantile(0.5) == 0.5
    p = h.percentiles()
    assert p["p50"] == 0.5 and p["p99"] == 50.0
    # exactness comes from the sample ring, not bucket interpolation
    assert h.quantile(0.0) == 0.05 and h.quantile(1.0) == 50.0


def test_prometheus_render_format():
    reg = MetricsRegistry()
    reg.counter("ksa_x_total", "things", labels=("cls",)).labels(
        cls="gpu").inc(4)
    reg.histogram("ksa_y_seconds", "lat", buckets=(1.0,)).observe(0.5)
    reg.register_callback("ksa_live", lambda: 7.0, "live")
    text = reg.render()
    assert "# HELP ksa_x_total things" in text
    assert "# TYPE ksa_x_total counter" in text
    assert 'ksa_x_total{cls="gpu"} 4' in text
    assert "# TYPE ksa_y_seconds histogram" in text
    assert 'ksa_y_seconds_bucket{le="1"} 1' in text
    assert 'ksa_y_seconds_bucket{le="+Inf"} 1' in text
    assert "ksa_y_seconds_sum 0.5" in text
    assert "ksa_y_seconds_count 1" in text
    assert "ksa_live 7" in text
    assert text.endswith("\n")


def test_topic_class_label():
    assert topic_class("t-new.gpu") == "gpu"
    assert topic_class("t-new.bigmem") == "bigmem"
    assert topic_class("t-new") == "flat"
    assert topic_class("t-done") == "flat"


def test_span_store_is_bounded_lru():
    store = SpanStore(max_tasks=3, max_spans_per_task=2)
    for i in range(5):
        store.add(f"t{i}", "submit", float(i))
    assert store.tasks() == ["t2", "t3", "t4"]  # t0, t1 LRU-evicted
    assert store.stats()["evicted_tasks"] == 2
    store.add("t4", "grant", 10.0)
    store.add("t4", "run", 11.0)  # over per-task cap: dropped, counted
    assert [s["name"] for s in store.trace("t4")] == ["submit", "grant"]
    assert store.stats()["dropped_spans"] == 1
    assert store.trace("unknown") == []
    # sorted by start, seq breaks ties; returned spans are copies
    store.add("tie", "b", 1.0)
    store.add("tie", "a", 1.0)
    chain = store.trace("tie")
    assert [s["name"] for s in chain] == ["b", "a"]
    chain[0]["name"] = "mutated"
    assert store.trace("tie")[0]["name"] == "b"


# ---------------------------------------------------------------------------
# span chains through the control plane
# ---------------------------------------------------------------------------

def test_flat_task_span_chain_and_http_surface():
    with KsaCluster(prefix="obs1", workers=1, worker_slots=2, http=True,
                    poll_interval_s=0.005) as c:
        tids = [c.submit("sleep", params={"duration": 0.01})
                for _ in range(3)]
        assert c.wait_all(tids, timeout=10.0)
        for tid in tids:
            names = [s["name"] for s in c.trace(tid)]
            assert names == ["submit", "grant", "claim", "run", "commit"]
            run = [s for s in c.trace(tid) if s["name"] == "run"][0]
            assert run["ok"] is True and run["attempt"] == 0
            assert run["dur_s"] >= 0.0
        port = c.http_port

        # GET / lists every route (the index is lint-checked below)
        body, _ = _get(port, "/")
        assert json.loads(body)["endpoints"] == list(ROUTES)

        # GET /metrics serves Prometheus text with per-class histograms
        body, ctype = _get(port, "/metrics")
        text = body.decode()
        assert ctype.startswith("text/plain")
        assert "0.0.4" in ctype
        assert re.search(
            r'ksa_task_queue_wait_seconds_bucket\{cls="cpu",le="\+Inf"\} 3',
            text)
        assert re.search(r'ksa_task_run_seconds_count\{cls="cpu"\} 3', text)
        assert re.search(r'ksa_result_commit_seconds_count\{cls="cpu"\} 3',
                         text)
        assert 'event="completed"' in text

        # GET /trace/<id> returns the chain; unknown ids are a 404
        body, _ = _get(port, f"/trace/{tids[0]}")
        payload = json.loads(body)
        assert payload["task_id"] == tids[0]
        assert [s["name"] for s in payload["spans"]] == \
            ["submit", "grant", "claim", "run", "commit"]
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(port, "/trace/no-such-task")
        assert err.value.code == 404


def test_preempted_and_retried_task_has_one_linked_chain():
    """ISSUE acceptance: KsaCluster.trace(task_id) returns the complete
    submit→terminal span chain for a preempted-and-retried task — attempt 0
    ends in a revoke(preempt) span, attempt 1 in run+commit, all under one
    task id and one trace_id."""
    big = PipelineSpec("obs-big", [
        Stage("work", "sleep", fan_out=1, params={"duration": 0.8},
              retry=RetryPolicy(max_attempts=3, timeout_s=60.0,
                                max_preemptions=6)),
    ])
    small = PipelineSpec("obs-small", [
        Stage("work", "sleep", fan_out=1, params={"duration": 0.05},
              retry=RetryPolicy(max_attempts=3, timeout_s=60.0)),
    ])
    with KsaCluster(prefix="obs2", workers=1, worker_slots=2,
                    poll_interval_s=0.005, lease=FairShare(preempt_factor=1.5),
                    max_in_flight_total=2) as c:
        bid = c.submit_campaign(big, list(range(6)), weight=1.0)
        time.sleep(0.3)
        sid = c.submit_campaign(small, list(range(2)), weight=4.0)
        assert c.wait_campaign(sid, timeout=60.0).state == "COMPLETED"
        assert c.wait_campaign(bid, timeout=120.0).state == "COMPLETED"
        assert c.pipeline.preemptions >= 1

        preempted = []
        for _stage, tids in c.pipeline.stage_tasks(bid):
            for tid in tids:
                if any(s["name"] == "revoke"
                       and s.get("reason") == RevokeReason.PREEMPT
                       for s in c.trace(tid)):
                    preempted.append(tid)
        assert preempted, "no preempted task left a revoke span"

        spans = c.trace(preempted[0])
        names = [(s["name"], s.get("attempt")) for s in spans]
        revoked_attempt = next(s["attempt"] for s in spans
                               if s["name"] == "revoke")
        # attempt n was granted then revoked for preemption ...
        assert ("grant", revoked_attempt) in names
        assert ("revoke", revoked_attempt) in names
        # ... and a later attempt of the SAME task id ran to commit
        terminal = [s for s in spans if s["name"] == "run" and s["ok"]]
        assert terminal and terminal[-1]["attempt"] > revoked_attempt
        # the terminal attempt reached a durable commit record: either the
        # monitor's commit span or the pipeline's journaled TaskDone
        assert any(s["name"] == "commit" or
                   (s["name"] == "journal" and s.get("event") == "TaskDone")
                   for s in spans)
        # every span that carries a trace id agrees on it
        tid0 = preempted[0]
        trace_ids = {s["trace_id"] for s in spans if "trace_id" in s}
        assert trace_ids == {tid0}
        # the registry agrees with the span story
        snap = c.broker.metrics.snapshot()
        revoked = snap["ksa_leases_revoked_total"]["series"]
        assert revoked[(RevokeReason.PREEMPT,)] == \
            c.broker.lease_stats()["revoked"]["preempt"] >= 1


def test_every_revoke_reason_is_counted_and_spanned():
    broker = Broker(default_partitions=2)
    try:
        sub = Submitter(broker, "rv")
        submitted = [sub.submit("sleep", params={"duration": 0.01})
                     for _ in RevokeReason.ALL]
        cons = Consumer(broker, ["rv-new.cpu"], group_id="rv-agents",
                        member_id="rv-m1")
        leased: list = []
        deadline = time.time() + 5.0
        while len(leased) < len(submitted) and time.time() < deadline:
            leased += [r.key for r in cons.lease(max_records=8, timeout=0.5)]
        assert sorted(leased) == sorted(submitted)
        tids = []
        for tid, reason in zip(leased, RevokeReason.ALL):
            assert broker.revoke_lease(tid, reason, requeue=False)
            tids.append((tid, reason))
        stats = broker.lease_stats()["revoked"]
        snap = broker.metrics.snapshot()
        series = snap["ksa_leases_revoked_total"]["series"]
        for tid, reason in tids:
            assert stats[reason] == 1
            assert series[(reason,)] == 1
            revokes = [s for s in broker.spans.trace(tid)
                       if s["name"] == "revoke"]
            assert len(revokes) == 1
            assert revokes[0]["reason"] == reason
            assert revokes[0]["requeued"] is False
    finally:
        broker.close()


def test_drain_keeps_counters_consistent():
    """Churn (graceful drain mid-burst) must not lose decrements: after the
    dust settles active leases are zero, grants == completions, and every
    task has exactly one ok run span."""
    with KsaCluster(prefix="obs3", workers=1, worker_slots=2,
                    poll_interval_s=0.005) as c:
        tids = [c.submit("sleep", params={"duration": 0.05})
                for _ in range(8)]
        w2 = c.add_worker(slots=2)
        time.sleep(0.1)
        assert c.drain_worker(w2, timeout_s=20.0)
        assert c.wait_all(tids, timeout=20.0)
        assert _wait(lambda: c.broker.lease_stats()["active"] == 0)
        stats = c.broker.lease_stats()
        assert stats["completed"] == len(tids)
        # every grant reached exactly one terminal: committed or revoked
        assert stats["granted"] == stats["completed"] + stats["failed"] + \
            stats["revoked_total"]
        for tid in tids:
            runs = [s for s in c.trace(tid) if s["name"] == "run" and s["ok"]]
            assert len(runs) == 1, f"{tid}: {runs}"
        # render-time callback gauge reflects the drained state
        assert "ksa_leases_active 0" in c.metrics_text()


def test_recover_refolds_journal_and_times_it():
    """Orchestrator crash + recover(): the journal fold shows up in the
    ksa_journal_fold_seconds histogram, journal counters keep counting on
    the successor, and finished tasks still have complete span chains."""
    broker = Broker(default_partitions=2)
    spec = PipelineSpec("obs-rec", [
        Stage("work", "sleep", fan_out=1, params={"duration": 0.05},
              retry=RetryPolicy(max_attempts=3, timeout_s=30.0)),
    ])
    try:
        from repro.core import WorkerAgent
        w = WorkerAgent(broker, "rc", slots=2, poll_interval_s=0.005).start()
        pipe1 = PipelineAgent(broker, "rc", poll_interval_s=0.005).start()
        cid = pipe1.submit_campaign(spec, list(range(6)))
        assert _wait(lambda: pipe1.status(cid).stages["work"].done >= 1,
                     timeout=30.0)
        pipe1.crash()

        pipe2 = PipelineAgent(broker, "rc", agent_id="rec2",
                              poll_interval_s=0.005).start()
        assert pipe2.recover([spec]) == [cid]
        st = pipe2.wait(cid, timeout=60.0)
        assert st.state == "COMPLETED", st.failure
        snap = broker.metrics.snapshot()
        fold = snap["ksa_journal_fold_seconds"]["series"][()]
        assert fold["count"] >= 1
        assert pipe2.events_journaled > 0
        # both agents fed the same per-agent journal counter family
        journal = snap["ksa_journal_events_total"]["series"]
        assert sum(journal.values()) >= pipe2.events_journaled
        for _stage, tids in pipe2.stage_tasks(cid):
            for tid in tids:
                names = [s["name"] for s in broker.spans.trace(tid)]
                assert "run" in names and "journal" in names
        pipe2.stop()
        w.stop()
    finally:
        broker.close()


# ---------------------------------------------------------------------------
# legacy views / schema stability
# ---------------------------------------------------------------------------

def test_legacy_stats_schemas_are_views_over_registry():
    cfg = None
    with KsaCluster(prefix="obs4", workers=1, worker_slots=2, http=True,
                    poll_interval_s=0.005) as c:
        tids = [c.submit("sleep", params={"duration": 0.01})
                for _ in range(4)]
        assert c.wait_all(tids, timeout=10.0)
        w = c.agents[0]
        s = w.stats()
        # pre-obs stats() keys unchanged (these are asserted across the
        # existing suite too — this is the canary)
        for key in ("agent_id", "kind", "state", "in_flight", "slots",
                    "completed", "failed", "rerouted", "deferred",
                    "requeued", "revoked", "dropped_revoked", "mem_revoked",
                    "heartbeat_failures"):
            assert key in s, key
        assert s["completed"] == w.tasks_completed == 4
        assert isinstance(w.tasks_completed, int)
        # the same number read through the registry
        snap = c.broker.metrics.snapshot()
        events = snap["ksa_agent_events_total"]["series"]
        assert events[(w.agent_id, "completed")] == 4
        summary = c.monitor.summary()
        for key in ("tasks", "done", "by_status", "results_handled",
                    "resubmissions", "revocations", "compactions",
                    "legacy_forwards", "duplicates_fenced"):
            assert key in summary, key
        assert summary["results_handled"] == \
            snap["ksa_monitor_events_total"]["series"][
                (c.monitor.monitor_id, "results_handled")]
        lease = c.broker.lease_stats()
        for key in ("granted", "completed", "failed", "requeued", "active",
                    "revoked", "revoked_total", "stale_drops"):
            assert key in lease, key
        assert set(lease["revoked"]) == set(RevokeReason.ALL)
        port = c.http_port
        body, _ = _get(port, "/summary")
        assert json.loads(body)["done"] == 4
        cfg = c.status()
    for key in ("prefix", "started", "agents", "broker", "leases", "monitor"):
        assert key in cfg, key


def test_monitor_route_index_lint():
    """Repo lint (pytest-collected): every literal route dispatched in
    MonitorAgent's do_GET must be listed in ROUTES (served by GET /), and
    vice versa — so the index payload can't silently rot."""
    import inspect

    import repro.core.monitor as monitor_mod
    src = inspect.getsource(monitor_mod)
    m = re.search(r"def do_GET\(self\).*", src, re.S)
    assert m, "could not locate do_GET dispatch block"
    body = m.group(0)
    dispatched = set(re.findall(r'parts == \["(\w+)"\]', body))
    dispatched |= set(re.findall(r'parts\[0\] == "(\w+)"', body))
    dispatched.add("")  # the `if not parts:` index route
    indexed = {r.strip("/").split("/")[0] for r in ROUTES}
    assert dispatched == indexed, (
        f"monitor routes drifted: dispatched={sorted(dispatched)} "
        f"vs ROUTES={sorted(indexed)}")


# ---------------------------------------------------------------------------
# RSS sampling (mem-overage policing measures, not trusts)
# ---------------------------------------------------------------------------

def test_sample_rss_mb_reads_kernel_accounting():
    rss = sample_rss_mb(cached=False)
    assert rss > 1.0  # a live CPython interpreter is many MB resident
    assert sample_rss_mb() == pytest.approx(sample_rss_mb(), rel=0.5)


def test_mem_used_is_measured_with_report_override():
    from repro.core.messages import TaskMessage

    class _Quiet(ClusterComputing):
        def run(self):
            return {}

    broker = Broker(default_partitions=1)
    from repro.core import Producer
    t = _Quiet(TaskMessage(task_id="m1", script="quiet"),
               Producer(broker), "mm", "agent-x")
    broker.close()
    # kernel-measured delta vs construction-time baseline: near zero for a
    # task that allocated nothing, never negative
    assert 0.0 <= t.mem_used_mb < 64.0
    # the legacy self-reporting hook remains as an explicit override
    t.report_mem(512.0)
    assert t.mem_used_mb == 512.0
    t.mem_used_mb = 1024.0
    assert t.mem_used_mb == 1024.0


# ---------------------------------------------------------------------------
# the obs switch and overhead posture
# ---------------------------------------------------------------------------

def test_obs_disabled_keeps_counters_but_drops_traces():
    reg = MetricsRegistry(enabled=False)
    h = reg.histogram("off_seconds")
    h.observe(1.0)
    assert h.count == 0 and h.quantile(0.5) is None
    c = reg.counter("off_total")
    c.inc()
    assert c.value == 1  # counters never turn off

    with KsaCluster(prefix="obs5", workers=1, worker_slots=2, obs=False,
                    poll_interval_s=0.005) as c:
        assert isinstance(c.broker.spans, NullSpanStore)
        tids = [c.submit("sleep", params={"duration": 0.0})
                for _ in range(3)]
        assert c.wait_all(tids, timeout=10.0)
        assert c.trace(tids[0]) == []
        # the legacy views still work: counters stay live
        assert c.broker.lease_stats()["completed"] == 3
        assert c.agents[0].tasks_completed == 3
        text = c.metrics_text()  # /metrics still serves, minus histogram data
        assert "ksa_leases_granted_total 3" in text
        # histogram series render but record nothing (null observations)
        assert 'ksa_task_run_seconds_count{cls="cpu"} 0' in text


def test_campaign_report_splits_queue_run_retry():
    spec = PipelineSpec("obs-rep", [
        Stage("a", "sleep", fan_out=1, params={"duration": 0.05}),
        Stage("b", "sleep", depends_on=("a",), params={"duration": 0.02}),
    ])
    with KsaCluster(prefix="obs6", workers=1, worker_slots=2,
                    poll_interval_s=0.005) as c:
        cid = c.submit_campaign(spec, list(range(3)))
        assert c.wait_campaign(cid, timeout=30.0).state == "COMPLETED"
        rep = c.campaign_report(cid)
        assert rep["campaign_id"] == cid and rep["state"] == "COMPLETED"
        assert list(rep["stages"]) == ["a", "b"]  # topological order
        a = rep["stages"]["a"]
        assert a["tasks"] == a["traced"] == 3
        assert a["run_s"] >= 3 * 0.04  # three 50 ms tasks actually ran
        assert a["queue_s"] >= 0.0 and a["retry_s"] == 0.0
        assert a["wall_s"] > 0.0
        assert rep["dominant_stage"] in ("a", "b")
        assert rep["wall_s"] >= max(s["run_s"] for s in
                                    rep["stages"].values()) / 2
        assert a["phases"] == {}  # the sleep task records no phase


@register_script("obs_phased")
class _PhasedComputing(ClusterComputing):
    """Two phases inside a task; the first attempt fails after its first
    phase when ``fail_first`` is set."""

    def run(self):
        slow = bool(self.params.get("fail_first")) and self.attempt == 0
        with phase("t.work"):
            time.sleep(0.15 if slow else 0.01)
        if slow:
            raise RuntimeError("first attempt fails")
        with phase("t.tail"):
            pass
        return {"attempt": self.attempt}


def test_phase_outside_a_task_stores_no_span():
    store = SpanStore()
    with phase("p.outside") as p:
        time.sleep(0.01)
    assert store.tasks() == [] and p.dur_s >= 0.009
    with task_scope(store, "t-1", 2):
        with phase("p.inside"):
            pass
        with phase("p.loop", task_span=False):
            pass
    with phase("p.after"):
        pass
    spans = store.trace("t-1")
    assert [(s["name"], s["task_id"], s["attempt"], s["parent"])
            for s in spans] == [("p.inside", "t-1", 2, "run")]


def test_phase_inside_a_task_is_a_child_of_its_run():
    with KsaCluster(prefix="obs15", workers=1, worker_slots=1,
                    poll_interval_s=0.005) as c:
        tid = c.submit("obs_phased")
        assert c.wait_all([tid], timeout=10.0)
        spans = c.trace(tid)
    run = [s for s in spans if s["name"] == "run"]
    kids = [s for s in spans if s.get("parent") == "run"]
    assert [s["name"] for s in kids] == ["t.work", "t.tail"]
    assert all(s["task_id"] == tid and s["attempt"] == run[0]["attempt"]
               for s in kids)
    assert run[0]["start"] <= kids[0]["start"] <= kids[-1]["end"] \
        <= run[0]["end"]


def test_phase_under_obs_off_records_nothing():
    store = NullSpanStore()
    with KsaCluster(prefix="obs16", workers=1, worker_slots=1, obs=False,
                    poll_interval_s=0.005) as c:
        tid = c.submit("obs_phased")
        assert c.wait_all([tid], timeout=10.0)
        assert c.trace(tid) == [] and c.broker.spans.tasks() == []
    with task_scope(store, "t-2", 0), phase("p.off"):
        pass
    assert store.trace("t-2") == [] and store.tasks() == []


def test_campaign_report_phases_sum_the_terminal_attempt():
    spec = PipelineSpec("obs-ph", [
        Stage("a", "obs_phased", fan_out=1, params={"fail_first": True},
              retry=RetryPolicy(max_attempts=2)),
    ])
    with KsaCluster(prefix="obs17", workers=1, worker_slots=2,
                    poll_interval_s=0.005) as c:
        cid = c.submit_campaign(spec, list(range(2)))
        assert c.wait_campaign(cid, timeout=30.0).state == "COMPLETED"
        a = c.campaign_report(cid)["stages"]["a"]
    assert a["retries"] == 2 and a["retry_s"] >= 2 * 0.15
    # both tasks' second attempts: 10 ms each, not the failed 150 ms
    assert set(a["phases"]) == {"t.work", "t.tail"}
    assert 2 * 0.009 <= a["phases"]["t.work"] < 0.15
    assert a["phases"]["t.work"] + a["phases"]["t.tail"] <= a["run_s"]


# ---------------------------------------------------------------------------
# telemetry plane: time-series store, SLO burn alerts, flight recorder,
# broker-streamed publisher/collector (ISSUE 9)
# ---------------------------------------------------------------------------

def _get_any(port, path):
    """GET that returns (status, parsed-json) for 2xx and 4xx alike."""
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}") as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_time_series_store_queries_and_validation():
    st = TimeSeriesStore(resolution_s=0.5)
    now = 100.0
    for i in range(10):
        st.ingest("m_total", {"site": "a"}, now + i, float(i), "counter")
        st.ingest("m_total", {"site": "b"}, now + i, float(2 * i), "counter")
        st.ingest("lat:p95", {"site": "a"}, now + i, 0.1 * i, "gauge")
    t = now + 9
    assert st.latest("m_total", {"site": "a"}) == 9.0
    assert st.sum_by("m_total", "site", now=t) == {"a": 9.0, "b": 18.0}
    assert st.sum("m_total", now=t) == 27.0
    # counter slope, per-series and summed across the label match
    assert st.rate("m_total", {"site": "a"}, 60.0, t) == pytest.approx(1.0)
    assert st.rate("m_total", None, 60.0, t) == pytest.approx(3.0)
    assert st.quantile("lat:p95", 1.0, None, 60.0, t) == pytest.approx(0.9)
    assert st.quantile("lat:p95", 0.5, None, 60.0, t) == pytest.approx(0.4)
    assert len(st.points("m_total", {"site": "a"}, 4.5, t)) == 5
    # the /query facade validates before it aggregates
    out = st.query("m_total", agg="sum_by", by="site", now=t)
    assert out["result"]["b"] == 18.0 and out["agg"] == "sum_by"
    assert st.query("m_total", agg="latest")["result"] == 9.0
    with pytest.raises(ValueError):
        st.query("m_total", agg="nope")
    with pytest.raises(ValueError):
        st.query("m_total", agg="quantile")     # requires q
    with pytest.raises(ValueError):
        st.query("m_total", agg="sum_by")       # requires by
    stats = st.stats()
    assert stats["series"] == 3


def test_time_series_store_same_bucket_folds_min_max_sum():
    st = TimeSeriesStore(resolution_s=10.0)
    for v in (1.0, 5.0, 3.0):
        st.ingest("g", None, 100.0, v, "gauge")
    pts = st.points("g")
    assert len(pts) == 1 and pts[0][1] == 3.0  # last write wins the sample
    assert st.latest("g") == 3.0


def test_alert_engine_multi_window_fire_and_resolve():
    store = TimeSeriesStore(resolution_s=0.1)
    now = 1000.0
    for i in range(11):                          # slope 2/s for 10 s
        store.ingest("err_total", None, now - 10 + i, float(2 * i), "counter")
    slo = SloSpec(name="errs", metric="err_total", kind="rate", objective=1.0)
    rule = AlertRule(slo=slo, long_window_s=20.0, short_window_s=5.0)
    reg = MetricsRegistry()
    fired = []
    eng = AlertEngine(store, [rule], registry=reg,
                      on_fire=lambda r, ev: fired.append(r))
    evs = eng.evaluate(now=now)
    assert evs[0]["breach"] and evs[0]["burn_short"] >= 1.0
    assert fired == ["errs"]
    assert [a["rule"] for a in eng.active()] == ["errs"]
    # still firing on the next pass, but no duplicate transition
    eng.evaluate(now=now + 0.5)
    assert [h["state"] for h in eng.status()["history"]] == ["firing"]
    # counter goes flat -> short-window burn decays -> resolves
    for i in range(11):
        store.ingest("err_total", None, now + i, 20.0, "counter")
    eng.evaluate(now=now + 10)
    st = eng.status()
    assert st["states"]["errs"]["state"] == "resolved"
    assert st["firing"] == []
    assert [h["state"] for h in st["history"]] == ["firing", "resolved"]
    text = reg.render()
    assert 'ksa_alerts_total{rule="errs",state="firing"} 1' in text
    assert 'ksa_alerts_total{rule="errs",state="resolved"} 1' in text


def test_slo_threshold_quantile_and_ratio_kinds():
    store = TimeSeriesStore(resolution_s=0.1)
    now = 50.0
    for i in range(10):
        store.ingest("wait:p95", None, now - 9 + i, 4.0, "gauge")
        store.ingest("bad_total", None, now - 9 + i, float(i), "counter")
        store.ingest("all_total", None, now - 9 + i, float(10 * i), "counter")
    q = SloSpec(name="p95", metric="wait:p95", objective=2.0, q=0.95)
    assert q.burn(store, 30.0, now) == pytest.approx(2.0)  # 4s vs 2s target
    ratio = SloSpec(name="errratio", metric="bad_total", kind="ratio",
                    total_metric="all_total", objective=0.05)
    assert ratio.burn(store, 30.0, now) == pytest.approx(2.0)  # 10% vs 5%
    # ratio with an empty denominator reads as zero burn, not a crash
    empty = SloSpec(name="e", metric="bad_total", kind="ratio",
                    total_metric="missing_total", objective=0.05)
    assert empty.burn(store, 30.0, now) == 0.0
    with pytest.raises(ValueError):
        SloSpec(name="x", metric="m", kind="ratio", objective=1.0)
    with pytest.raises(ValueError):
        SloSpec(name="x", metric="m", objective=0.0)
    with pytest.raises(ValueError):
        AlertRule(slo=q, long_window_s=5.0, short_window_s=10.0)


def test_flight_recorder_ring_drain_and_storm_autodump():
    fr = FlightRecorder(max_events=32, storm_threshold=5,
                        storm_window_s=60.0, storm_cooldown_s=0.0)
    fr.context_fn = lambda: {"extra": 1}
    for i in range(4):
        fr.record("grant", holder=f"w{i}")
    seq, evs = fr.since(0)
    assert [e["kind"] for e in evs] == ["grant"] * 4 and seq == 4
    seq2, evs2 = fr.since(seq)                   # incremental drain
    assert (seq2, evs2) == (4, [])
    for i in range(5):
        fr.record("revocation", task_id=f"t{i}", reason="preempt")
    dumps = fr.dumps()
    assert [d["trigger"] for d in dumps] == ["revocation_storm"]
    revs = [e for e in dumps[0]["events"] if e["kind"] == "revocation"]
    assert len(revs) == 5
    assert all(e["reason"] == "preempt" for e in revs)
    assert dumps[0]["context"]["extra"] == 1     # injected live context
    assert fr.stats()["counts"] == {"grant": 4, "revocation": 5}
    snap = fr.snapshot()
    assert snap["seq"] == 9 and len(snap["dumps"]) == 1


def test_telemetry_topic_schema_and_collector_fold():
    with KsaCluster(prefix="obs7", workers=1, telemetry=True) as c:
        ids = [c.submit("sleep", params={"duration": 0.01}) for _ in range(3)]
        assert c.wait_all(ids, timeout=30.0)
        c.telemetry_publisher.publish_once()
        topic = topic_names("obs7")["telemetry"]
        recs = c.broker.read_from(topic, 0)
        assert recs, "publisher produced nothing on the telemetry topic"
        rec = recs[-1].value
        assert rec["kind"] == "telemetry" and rec["v"] == 1
        for key in ("source", "site", "seq", "ts", "metrics", "spans",
                    "events"):
            assert key in rec
        by_type = {}
        for row in rec["metrics"]:
            by_type.setdefault(row["type"], []).append(row)
        assert {"value"} <= set(by_type["counter"][0])
        hist = by_type["histogram"][0]
        assert {"count", "sum", "p50", "p95", "p99"} <= set(hist)
        # collector folds the records into queryable series
        c.telemetry_collector.poll()
        st = c.telemetry_store
        assert st.sum("ksa_leases_completed_total") >= 3
        names = set(st.series_names())
        assert "ksa_task_queue_wait_seconds:p95" in names   # digest series
        assert "ksa_task_queue_wait_seconds_count" in names
        # the facade query sees the same numbers
        out = c.query("ksa_leases_completed_total", agg="sum")
        assert out["result"] >= 3


def test_restarted_collector_rebuilds_store_from_topic_replay():
    """Killing the monitor (the collector's host) loses nothing: a fresh
    collector replays the durable PREFIX-telemetry topic from offset 0 via
    Broker.read_from and rebuilds the exact same series."""
    with KsaCluster(prefix="obs8", workers=1, telemetry=True) as c:
        ids = [c.submit("sleep", params={"duration": 0.01}) for _ in range(5)]
        assert c.wait_all(ids, timeout=30.0)
        c.telemetry_publisher.publish_once()
        c.telemetry_collector.poll()
        live = c.telemetry_store
        granted = live.sum("ksa_leases_granted_total")
        assert granted >= 5
        c.monitor.stop()                          # kill the collector host
        store2 = TimeSeriesStore()
        coll2 = TelemetryCollector(c.broker, topic_names("obs8")["telemetry"],
                                   store=store2)
        n = coll2.poll()
        assert n > 0
        assert store2.sum("ksa_leases_granted_total") == granted
        # no gap: every series the live store knew is rebuilt
        assert set(store2.series_names()) >= set(live.series_names())


def test_revocation_storm_fires_alert_and_dumps_blackbox():
    slo = SloSpec(name="revocation-rate", metric="ksa_leases_revoked_total",
                  kind="rate", objective=0.2)
    rule = AlertRule(slo=slo, long_window_s=60.0, short_window_s=30.0)
    with KsaCluster(prefix="obs9", workers=2, worker_slots=6,
                    telemetry=True, slos=[rule]) as c:
        ids = [c.submit("hang") for _ in range(12)]
        # keyed partitioning can split unevenly, so not all 12 lease at
        # once — wait for a storm's worth and revoke whatever is active
        # (revoking frees slots, so the queued remainder leases next)
        assert _wait(lambda: c.broker.lease_stats()["active"] >= 10,
                     timeout=15.0)
        c.telemetry_publisher.publish_once()      # pre-storm sample
        revoked = [t for t in ids[:6]
                   if c.revoke(t, reason=RevokeReason.PREEMPT,
                               requeue=False)]
        time.sleep(0.3)
        c.telemetry_publisher.publish_once()      # mid-storm sample
        pending = [t for t in ids if t not in revoked]
        deadline = time.time() + 8.0
        while len(revoked) < 12 and time.time() < deadline:
            for tid in list(pending):
                if c.revoke(tid, reason=RevokeReason.PREEMPT,
                            requeue=False):
                    revoked.append(tid)
                    pending.remove(tid)
            time.sleep(0.05)
        assert len(revoked) >= 10                 # a storm's worth
        time.sleep(0.3)
        c.telemetry_publisher.publish_once()
        c.telemetry_collector.poll()
        c.alert_engine.evaluate()
        # the burn-rate alert fired on the revocation counter's slope
        assert _wait(lambda: "revocation-rate" in
                     c.alert_engine.status()["firing"], timeout=5.0)
        assert [a["rule"] for a in c.status()["alerts"]] == \
            ["revocation-rate"]
        assert 'ksa_alerts_total{rule="revocation-rate",state="firing"} 1' \
            in c.metrics_text()
        # 12 revocations inside the storm window auto-latched a blackbox
        # dump; the alert firing latched a second one
        triggers = [d["trigger"] for d in c.broker.blackbox.dumps()]
        assert "revocation_storm" in triggers
        assert "alert:revocation-rate" in triggers
        storm = next(d for d in c.broker.blackbox.dumps()
                     if d["trigger"] == "revocation_storm")
        revs = [e for e in storm["events"] if e["kind"] == "revocation"]
        assert len(revs) >= 10
        assert all(e["reason"] == RevokeReason.PREEMPT for e in revs)
        assert {e["task_id"] for e in revs} <= set(ids)
        assert "leases" in storm["context"]       # injected cluster context
        # a forced dump works with or without telemetry and is retained
        manual = c.dump_blackbox()
        assert manual["trigger"] == "manual"
        assert manual in c.broker.blackbox.dumps()


def test_monitor_query_alerts_blackbox_endpoints():
    slo = SloSpec(name="qw-p95", metric="ksa_task_queue_wait_seconds:p95",
                  objective=30.0, q=0.95)
    with KsaCluster(prefix="obs10", workers=1, http=True,
                    telemetry=True, slos=[slo]) as c:
        port = c.http_port
        ids = [c.submit("sleep", params={"duration": 0.01}) for _ in range(3)]
        assert c.wait_all(ids, timeout=30.0)
        c.telemetry_publisher.publish_once()
        c.telemetry_collector.poll()
        code, data = _get_any(
            port, "/query?name=ksa_leases_completed_total&agg=sum")
        assert code == 200 and data["result"] >= 3
        code, data = _get_any(
            port, "/query?name=ksa_task_queue_wait_seconds:p95"
                  "&agg=quantile&q=0.95&window_s=120")
        assert code == 200 and data["result"] is not None
        # label filter: l.<key>=<value>
        code, data = _get_any(
            port, "/query?name=ksa_leases_granted_total&agg=sum&l.cls=cpu")
        assert code == 200
        code, data = _get_any(port, "/alerts")
        assert code == 200 and data["rules"] == ["qw-p95"]
        assert data["firing"] == []               # 30 s objective holds
        code, data = _get_any(port, "/blackbox")
        assert code == 200
        assert any(e["kind"] == "grants" for e in data["events"])
        # /query, /alerts, /blackbox are advertised on the index
        code, data = _get_any(port, "/")
        assert {"/query", "/alerts", "/blackbox"} <= set(data["endpoints"])


def test_monitor_http_error_paths_are_structured_json():
    """Unknown /trace and /campaigns ids and malformed /query parameters
    come back as structured JSON 404/400 payloads, never empty bodies."""
    with KsaCluster(prefix="obs11", workers=1, http=True,
                    telemetry=True) as c:
        port = c.http_port
        for path in ("/trace/no-such-task", "/campaigns/no-such-campaign",
                     "/tasks/no-such-task"):
            code, data = _get_any(port, path)
            assert code == 404, path
            assert data["error"], path            # human-readable message
        bad_queries = [
            "/query",                              # missing name
            "/query?agg=rate",                     # still missing name
            "/query?name=m&agg=bogus",             # unknown aggregation
            "/query?name=m&window_s=abc",          # non-numeric window
            "/query?name=m&q=x&agg=quantile",      # non-numeric q
            "/query?name=m&agg=quantile",          # quantile without q
            "/query?name=m&agg=sum_by",            # sum_by without by
            "/query?name=m&bogus=1",               # unknown parameter
        ]
        for path in bad_queries:
            code, data = _get_any(port, path)
            assert code == 400, path
            assert data["error"] == "bad query" and data["detail"], path
    # without a telemetry plane the query surface 404s instead of crashing
    with KsaCluster(prefix="obs12", workers=0, http=True) as c2:
        port = c2.http_port
        code, data = _get_any(port, "/query?name=m")
        assert code == 404 and "telemetry" in data["error"]
        code, data = _get_any(port, "/alerts")
        assert code == 404 and "alert" in data["error"]
        code, data = _get_any(port, "/blackbox")
        assert code == 200                        # blackbox is always on
        with pytest.raises(RuntimeError):
            c2.query("m")
        with pytest.raises(RuntimeError):
            c2.alerts()


def test_autoscale_sensing_reads_from_time_series_store():
    """The autoscaler's backlog/drain-rate history lives in a
    TimeSeriesStore; with the telemetry plane on it shares the cluster's
    store, so /query can read the controller's own sensing series."""
    from repro.autoscale import AutoscaleConfig, PoolSpec
    cfg = AutoscaleConfig(pools=(PoolSpec("cpu", min_agents=1,
                                          max_agents=2),))
    with KsaCluster(prefix="obs13", workers=0, telemetry=True,
                    autoscale=cfg) as c:
        assert c.autoscaler.store is c.telemetry_store  # shared, not private
        ids = [c.submit("sleep", params={"duration": 0.01}) for _ in range(4)]
        c.autoscaler.tick()
        assert c.wait_all(ids, timeout=30.0)
        c.autoscaler.tick()
        out = c.query("ksa_pool_backlog", agg="points",
                      labels={"pool": "cpu", "src": "autoscale"})
        assert out["result"], "controller sensing did not land in the store"
        rate = c.query("ksa_pool_consumed_total", agg="rate",
                       labels={"pool": "cpu", "src": "autoscale"},
                       window_s=30.0)
        assert rate["result"] >= 0.0


# ---------------------------------------------------------------------------
# satellite: Prometheus text-format conformance lint
# ---------------------------------------------------------------------------

_PROM_PAIR = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)='
                        r'"((?:[^"\\\n]|\\\\|\\"|\\n)*)"')
_PROM_SAMPLE = re.compile(r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})? (\S+)$')


def _lint_prometheus(text):
    """Parse + lint a Prometheus 0.0.4 exposition. Returns the samples as
    (name, labels, value) triples; asserts on any conformance violation."""
    help_count, type_count, types = {}, {}, {}
    samples = []
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            name = line.split(" ", 3)[2]
            help_count[name] = help_count.get(name, 0) + 1
        elif line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            type_count[name] = type_count.get(name, 0) + 1
            types[name] = kind
        elif line.startswith("#"):
            continue
        else:
            m = _PROM_SAMPLE.match(line)
            assert m, f"unparseable sample line: {line!r}"
            name, braces, value = m.groups()
            labels = {}
            if braces:
                body, pos = braces[1:-1], 0
                while pos < len(body):  # strict: every char must be covered
                    pm = _PROM_PAIR.match(body, pos)
                    assert pm, f"bad label escaping in {line!r}"
                    labels[pm.group(1)] = pm.group(2)
                    pos = pm.end()
                    if pos < len(body):
                        assert body[pos] == ",", line
                        pos += 1
            float(value)  # every sample value must parse
            samples.append((name, labels, value))
    families = {}
    for name, labels, value in samples:
        fam = name
        for suffix in ("_bucket", "_count", "_sum"):
            if name.endswith(suffix) and \
                    types.get(name[:-len(suffix)]) == "histogram":
                fam = name[:-len(suffix)]
                break
        families.setdefault(fam, []).append((name, labels, value))
    for fam in families:
        if not fam.startswith("ksa_"):
            continue
        assert help_count.get(fam) == 1, \
            f"{fam}: {help_count.get(fam, 0)} HELP lines (want exactly 1)"
        assert type_count.get(fam) == 1, \
            f"{fam}: {type_count.get(fam, 0)} TYPE lines (want exactly 1)"
    for fam, kind in types.items():
        if kind != "histogram":
            continue
        counts, infs = {}, {}
        for name, labels, value in families.get(fam, []):
            child = tuple(sorted((k, v) for k, v in labels.items()
                                 if k != "le"))
            if name == fam + "_count":
                counts[child] = float(value)
            elif name == fam + "_bucket" and labels.get("le") == "+Inf":
                infs[child] = float(value)
        assert set(counts) == set(infs), \
            f"{fam}: children missing a +Inf bucket or a _count"
        for child in counts:
            assert counts[child] == infs[child], \
                f"{fam}{dict(child)}: le=\"+Inf\" != _count"
    return samples


def test_prometheus_lint_escapes_label_values():
    reg = MetricsRegistry()
    raw = 'we"ird\\pa\nth'
    reg.counter("ksa_esc_total", "escape check", labels=("path",)) \
        .labels(path=raw).inc()
    reg.histogram("ksa_esc_seconds", "escape hist", labels=("path",)) \
        .labels(path=raw).observe(0.2)
    samples = _lint_prometheus(reg.render())
    escaped = [lab["path"] for name, lab, _ in samples
               if name == "ksa_esc_total"]
    assert escaped == ['we\\"ird\\\\pa\\nth']  # \  " and newline escaped


def test_prometheus_conformance_cluster_and_federation_renders():
    with KsaCluster(prefix="obs14", workers=1, telemetry=True) as c:
        ids = [c.submit("sleep", params={"duration": 0.01}) for _ in range(3)]
        assert c.wait_all(ids, timeout=30.0)
        text = c.metrics_text()
        samples = _lint_prometheus(text)
        assert any(n.startswith("ksa_") for n, _, _ in samples)
        # federation merge: every sample gains a site label; the merged
        # exposition must still be conformant with deduped HELP/TYPE
        merged = merge_renders({"home": text, "edge": text})
        msamples = _lint_prometheus(merged)
        tagged = [lab for n, lab, _ in msamples if n.startswith("ksa_")]
        assert tagged and all(lab.get("site") in ("home", "edge")
                              for lab in tagged)


# ---------------------------------------------------------------------------
# satellite: metrics catalog lint (docs/METRICS.md)
# ---------------------------------------------------------------------------

def test_metrics_catalog_documents_every_registered_family():
    import pathlib
    from repro.obs.catalog import _full_registry, catalog_names, \
        render_catalog
    doc = pathlib.Path(__file__).resolve().parent.parent / "docs/METRICS.md"
    assert doc.exists(), "docs/METRICS.md missing — regenerate with " \
        "PYTHONPATH=src python -m repro.obs.catalog > docs/METRICS.md"
    documented = catalog_names(doc.read_text())
    reg = _full_registry()
    registered = {r["name"] for r in reg.describe()
                  if r["name"].startswith("ksa_")}
    missing = registered - documented
    assert not missing, \
        f"metrics missing from docs/METRICS.md: {sorted(missing)} — " \
        f"regenerate with PYTHONPATH=src python -m repro.obs.catalog"
    # the generator output itself round-trips through the lint
    assert catalog_names(render_catalog(reg)) == registered
