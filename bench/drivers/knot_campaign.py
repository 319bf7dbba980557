"""Driver for knot-screen campaigns: the paper's screen -> localize ->
aggregate DAG (``knots_pipeline``) on a ``KsaCluster`` with one worker.

Set-up runs every batch of the run's plan once, as one campaign: that
compiles or loads every writhe shape the window will meet (the kept and
survivor counts of these batches) and fills the caches. The window is a
closed loop: campaigns whose batches are seeded permutations of the plan,
submitted so that one more is always queued. It opens at the first batch
completion and closes at the first completion at or after ``seconds``
later; ``structures_per_s`` counts the structures of the batches that
completed in between (a batch completes when its localize task commits or
is skipped). That rate is host-bound and reported per layer
(``knot.structures_per_s``). The end-to-end run traces its whole window on
the profiler; ``chip_ms_per_kstructure`` is the device time of the screen
and localize tasks that ran wholly inside the window per 1000 structures
those screens took in. After the close the worker drains its running task,
and every answer committed in the run is checked against the plain
reference.
"""
from __future__ import annotations

import gc
import time
from collections import deque

import numpy as np

import loadgen
import stats
import tracing
from harness import Run, log, peak_bytes

POLL_S = 0.05
REF_CHUNK = 256


def run(r: Run) -> None:
    import jax
    from repro.apps import knots
    from repro.cluster import KsaCluster
    from repro.core.scheduling import ResourceProfile

    cfg, mix = r.config, r.mix
    size, npts = int(cfg["batch_size"]), int(cfg["n_points"])
    q = r.reference.quality_table()
    plan = loadgen.campaign_plan(
        mix, r.seed, size,
        lambda ids: q[np.asarray(ids) % len(q)] >= cfg["quality_threshold"])
    orders = loadgen.campaign_orders(mix, r.seed)
    spec = knots.knots_pipeline(size, n_points=npts,
                                max_in_flight=int(cfg["max_in_flight"]))
    localize_mb = spec.stages["localize"].resources.mem_mb
    trace_s = float(mix["trace_s"])
    capture = tracing.Capture(r.out_dir / "trace")

    def items(order):
        return [i for k in order for i in plan[k]]

    campaigns: dict[str, list[int]] = {}
    tasks: list[dict] = []
    reports: dict[str, dict] = {}

    def harvest(c, cid):
        """Read a campaign's tasks and report while the pipeline agent
        still holds it (it keeps a bounded number of finished ones)."""
        tasks.extend(_collect(c, cid))
        reports[cid] = c.campaign_report(cid)

    with KsaCluster(prefix="bench") as c:
        worker = c.add_worker(slots=int(cfg["worker_slots"]),
                              profile=ResourceProfile(cpus=2,
                                                      mem_mb=localize_mb))
        warm = list(range(len(plan)))
        cid = c.submit_campaign(spec, items(warm))
        campaigns[cid] = warm
        st = c.wait_campaign(cid, timeout=float(mix["setup_timeout_s"]))
        if st.state != "COMPLETED":
            raise RuntimeError(f"set-up campaign ended {st.state}: "
                               f"{st.failure}")
        at_close = {cid: st}
        harvest(c, cid)
        t_submit = r.setup_done()
        r.readings["setup_compiles"] = r.compiles.total
        r.compiles.counting = True

        outstanding: deque[str] = deque()
        seen: dict[str, int] = {}
        finished: dict[str, float] = {}
        edges: list[tuple[float, float]] = []

        def submit():
            order = next(orders)
            cid = c.submit_campaign(spec, items(order))
            campaigns[cid] = order
            seen[cid] = 0
            outstanding.append(cid)

        if not r.trace:
            capture.start()
        for _ in range(int(mix["campaigns_outstanding"])):
            submit()
        t_open = closed = None
        while closed is None:
            time.sleep(POLL_S)
            now = time.time()
            for cid in list(outstanding):
                st = c.campaign_status(cid)
                loc = st.stages["localize"]
                n = loc.done + loc.skipped
                if n > seen[cid]:
                    edges.append((now, float((n - seen[cid]) * size)))
                    seen[cid] = n
                if st.done:
                    finished[cid] = now
                    outstanding.remove(cid)
                    if st.state != "COMPLETED":
                        raise RuntimeError(f"campaign {cid} ended "
                                           f"{st.state}: {st.failure}")
                    at_close[cid] = st
                    harvest(c, cid)
                    submit()
            if t_open is None and edges:
                t_open = edges[0][0]
                cpu_open, gc_open = time.process_time(), _gc_runs()
            if t_open is None:
                if now - t_submit > float(mix["setup_timeout_s"]):
                    raise TimeoutError("no batch completed")
                continue
            if (capture.wall_open is None
                    and now >= t_open + r.seconds - trace_s):
                capture.start()
            closed = stats.rate_between_edges(edges, t_open, r.seconds)
            if closed is None and now - t_open > r.seconds + \
                    float(mix["setup_timeout_s"]):
                raise TimeoutError("the window did not close")
        t_close = t_open + closed[1]
        cpu_s, gc_runs = time.process_time() - cpu_open, _gc_runs() - gc_open
        r.compiles.counting = False
        r.readings["structures_per_s"] = closed[0] / closed[1]
        at_close.update((cid, c.campaign_status(cid))
                        for cid in outstanding)
        capture.stop()
        c.drain_worker(worker, timeout_s=float(mix["setup_timeout_s"]))
        for cid in outstanding:
            harvest(c, cid)
        r.readings["campaign_reports"] = [
            reports[cid] for cid, t in finished.items()
            if t_open < t <= t_close]
        spans = _run_spans(c, tasks)
    r.memory_peak_bytes = peak_bytes(jax.devices())
    r.readings.update(
        compiles_in_window=r.compiles.compiles + r.compiles.cache_loads,
        device_kind=jax.devices()[0].device_kind, n_points=npts,
        task_spans=spans)
    tr = capture.load()
    if r.trace:
        tr.add_wall_spans((f"ksa.{s}", a, b) for s, a, b, _ in spans)
        r.readings["trace"] = tr
    else:
        r.e2e["chip_ms_per_kstructure"] = chip_ms_per_kstructure(
            tr, spans, t_open, t_close, size)
    batches_in_window = sum(1 for t, _ in edges if t_open < t <= t_close)
    log(f"window {closed[1]:.3f}s: {batches_in_window} batches, "
        f"{len(campaigns)} campaigns, compiles in window "
        f"{r.readings['compiles_in_window']}")
    log(window_profile(spans, t_open, t_close, cpu_s, gc_runs))

    ref = reference_answers(r.reference, plan, cfg, "float32", q)
    r.evidence.update(plan=plan, ref=ref, q=q)
    screens, localizes, aggregates = program_answers(tasks, plan, campaigns)
    counts = compare(ref, screens, localizes, aggregates, cfg)
    counts["delivery_errors"] = delivery_errors(at_close, tasks, campaigns)
    ran = [t for t in tasks if t["stage"] == "screen"
           and (t["result"] is not None
                or t["status"] in ("RUNNING", "ERROR", "TIMEOUT"))]
    r.attempted = len(ran)
    r.failed = sum(1 for t in ran if t["result"] is None)
    for name, limit in cfg["limits"].items():
        r.check(name, counts[name], limit)


# ---------------------------------------------------------------------------
# what the program answered
# ---------------------------------------------------------------------------


def _gc_runs() -> int:
    return sum(g["collections"] for g in gc.get_stats())


def window_profile(spans, t_open, t_close, cpu_s, gc_runs) -> str:
    """Where the window's time went on the host: the share of it in which
    the worker ran a task, the screen's median run time, this process's
    CPU seconds per wall second and its garbage collections. A slow
    window with the worker as busy and the CPU share as high is slower
    work; one with the worker idle lost time between tasks."""
    wall = t_close - t_open
    busy = sum(max(0.0, min(b, t_close) - max(a, t_open))
               for _, a, b, _ in spans)
    screen = sorted(b - a for s, a, b, _ in spans
                    if s == "screen" and t_open <= a < t_close)
    med = 1000 * screen[len(screen) // 2] if screen else float("nan")
    return (f"window profile: worker busy {busy / wall:.3f}, screen median "
            f"{med:.1f} ms over {len(screen)}, cpu/wall {cpu_s / wall:.3f}, "
            f"gc runs {gc_runs}")


def chip_ms_per_kstructure(tr, spans, t_open, t_close, size: int
                           ) -> float | None:
    """Device time of the screen and localize tasks whose run lay wholly in
    [t_open, t_close], per 1000 of the ``size`` structures each of those
    screens took in; ``None`` where the trace holds no device operation or
    no screen ran inside. Each task is whole, so the number does not hang
    on where the window's edges fall in a batch."""
    if not any(tr.ops):
        return None
    ns, screens = 0.0, 0
    for stage, a, b, _ in spans:
        if t_open <= a and b <= t_close and stage in ("screen", "localize"):
            ns += sum(tracing.union_ns(d, tr.to_ns(a), tr.to_ns(b))
                      for d in tr.ops) / len(tr.ops)
            screens += stage == "screen"
    return ns / 1e6 / (screens * size / 1000) if screens else None


def _collect(c, cid) -> list[dict]:
    out = []
    for stage, tids in c.pipeline.stage_tasks(cid):
        for tid in tids:
            e = c.task(tid)
            if e is None or e.task is None:
                continue
            out.append({"campaign": cid, "stage": stage, "task_id": tid,
                        "params": e.task.params, "result": e.result,
                        "status": e.status,
                        "duplicates": e.duplicate_results,
                        "errors": len(e.errors)})
    return out


def _run_spans(c, tasks) -> list[tuple]:
    """(stage, wall start, wall end, structures) of every task's run."""
    out = []
    for t in tasks:
        if t["result"] is None:
            continue
        n = (t["result"].get("kept") if t["stage"] == "screen"
             else t["result"].get("candidates"))
        for s in c.broker.spans.trace(t["task_id"]):
            if s["name"] == "run":
                out.append((t["stage"], s["start"], s["end"], n))
    return out


def program_answers(tasks, plan, campaigns):
    first = {b[0]: k for k, b in enumerate(plan)}
    screens, localizes, aggregates = [], [], []
    for t in tasks:
        res, params = t["result"], t["params"]
        if t["stage"] == "screen":
            screens.append({"batch": first.get(params["batch"][0]),
                            "result": res})
        elif t["stage"] == "localize" and res is not None:
            up = params.get("upstream") or {}
            localizes.append({"survivors": [int(i) for i in
                                            up.get("knotted", [])],
                              "candidates": res.get("candidates"),
                              "cores": res.get("cores", {})})
        elif t["stage"] == "aggregate" and res is not None:
            aggregates.append({"batches": campaigns[t["campaign"]],
                               "result": res})
    return screens, localizes, aggregates


def delivery_errors(statuses, tasks, campaigns) -> int:
    """Tasks lost, duplicated, errored, retried or revoked in the window's
    campaigns (the set-up campaign included)."""
    bad = 0
    for cid, st in statuses.items():
        for s in st.stages.values():
            bad += s.errors + s.failed + s.duplicates + s.retried + s.revoked
        if st.state == "COMPLETED":
            for s in st.stages.values():
                bad += abs(s.expected - s.done - s.skipped)
            if st.stages["localize"].done + st.stages["localize"].skipped \
                    != st.stages["screen"].done:
                bad += 1
    bad += sum(t["duplicates"] + t["errors"] for t in tasks)
    return bad


# ---------------------------------------------------------------------------
# the reference, and the comparison
# ---------------------------------------------------------------------------


def reference_answers(ref, plan, cfg, dtype: str, q=None) -> list[dict]:
    """Per plan batch: kept ids, writhe per kept id, knotted ids, the ids
    near the threshold, and knot cores (with their decision margins) for
    every id within ``tol`` of knotted."""
    import jax.numpy as jnp

    thr, tol = float(cfg["writhe_knot_threshold"]), float(cfg["margin"])
    npts, min_len = int(cfg["n_points"]), int(cfg["core_min_len"])
    q = ref.quality_table() if q is None else q
    fn = ref.writhe_fn(dtype)
    out = []
    for ids in plan:
        kept = [i for i in ids if q[i % len(q)] >= cfg["quality_threshold"]]
        wr = np.zeros(len(kept))
        cores, core_near = {}, set()
        for lo in range(0, len(kept), REF_CHUNK):
            part = kept[lo:lo + REF_CHUNK]
            coords = ref.structures(part, npts)
            pad = REF_CHUNK - len(part)
            if pad:
                coords = np.concatenate([coords, np.repeat(coords[:1], pad,
                                                           0)])
            w_tot, wmap = fn(jnp.asarray(coords))
            w_tot = np.asarray(w_tot)[:len(part)]
            wr[lo:lo + len(part)] = w_tot
            cand = np.nonzero(np.abs(w_tot) >= thr - tol)[0]
            if len(cand):
                maps = np.asarray(wmap[jnp.asarray(cand)])
                for j, m in zip(cand, maps):
                    core, margin = ref.knot_core(m, thr, min_len)
                    cores[part[j]] = core
                    if margin <= tol:
                        core_near.add(part[j])
        a = np.abs(wr)
        out.append({"kept": kept,
                    "wr": dict(zip(kept, wr.tolist())),
                    "knotted": {i for i, x in zip(kept, a) if x >= thr},
                    "near": {i for i, x in zip(kept, a)
                             if abs(x - thr) <= tol},
                    "cores": cores, "core_near": core_near})
    return out


def compare(ref, screens, localizes, aggregates, cfg) -> dict:
    """The numbers ``correct`` is decided on (each against its limit)."""
    size = int(cfg["batch_size"])
    wr_gap, verdicts, cores_bad, counts = 0.0, 0, 0, 0
    near = set().union(*(b["near"] for b in ref))
    ref_cores, core_near = {}, set()
    for b in ref:
        ref_cores.update(b["cores"])
        core_near |= b["core_near"]

    def core_errors(ids, got: dict) -> int:
        bad = 0
        for i in ids:
            if i in near or i in core_near or i not in ref_cores:
                continue
            want = ref_cores[i]
            have = got.get(str(i))
            if (None if have is None else tuple(have)) != want:
                bad += 1
        return bad

    for s in screens:
        res = s["result"]
        if res is None:
            continue
        if s["batch"] is None:
            counts += 1
            continue
        b = ref[s["batch"]]
        counts += (res["processed"] != size) + (res["kept"] != len(b["kept"]))
        got = {int(i) for i in res["knotted"]}
        verdicts += len((got ^ b["knotted"]) - b["near"])
        for i, w in res.get("wr", {}).items():
            i = int(i)
            if i not in b["wr"]:
                counts += 1
                continue
            wr_gap = max(wr_gap, abs(float(w) - b["wr"][i]))
    for loc in localizes:
        counts += loc["candidates"] != len(loc["survivors"])
        cores_bad += core_errors(loc["survivors"], loc["cores"])
    for agg in aggregates:
        res = agg["result"]
        bs = [ref[k] for k in agg["batches"]]
        counts += (res["processed"] != size * len(bs)) + \
            (res["kept"] != sum(len(b["kept"]) for b in bs)) + \
            (res["batches"] != len(bs))
        want = set().union(*(b["knotted"] for b in bs))
        got = {int(i) for i in res["knotted"]}
        verdicts += len((got ^ want) - near)
        cores_bad += core_errors(sorted(want | got), res["cores"])
    return {"wr_gap": wr_gap, "verdict_errors": verdicts,
            "core_errors": cores_bad, "count_errors": counts}


def control_checks(r: Run) -> dict:
    """The numbers the control gives: the reference in bfloat16 put in the
    program's place, compared as the program's answers are."""
    ev = r.evidence
    screens, localizes = control_answers(r.reference, ev["plan"], r.config,
                                         "bfloat16", ev["q"])
    return compare(ev["ref"], screens, localizes, [], r.config)


def control_answers(ref, plan, cfg, dtype: str, q=None):
    """The reference computed in ``dtype``, put in the program's place:
    answers shaped as the program's screen and localize results."""
    ctl = reference_answers(ref, plan, cfg, dtype, q)
    thr = float(cfg["writhe_knot_threshold"])
    screens, localizes = [], []
    for k, (ids, b) in enumerate(zip(plan, ctl)):
        knotted = sorted(b["knotted"])
        screens.append({"batch": k, "result": {
            "processed": len(ids), "kept": len(b["kept"]),
            "knotted": knotted,
            "wr": {str(i): b["wr"][i] for i in knotted}}})
        localizes.append({"survivors": knotted, "candidates": len(knotted),
                          "cores": {str(i): list(b["cores"][i])
                                    for i in knotted
                                    if b["cores"].get(i) is not None
                                    and abs(b["wr"][i]) >= thr}})
    return screens, localizes
