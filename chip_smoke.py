#!/usr/bin/env python3
"""Chip smoke test: the system's two main paths, once each, on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the multi-chip paths, on four chips

One chip:

1. **knot campaign** — the paper's screen -> localize -> aggregate DAG
   (``knots_pipeline``) through ``KsaCluster.run_campaign`` with an
   in-process worker: 3 batches of 4000 structures (the paper's batch) at
   256 points. The stage-1 writhe map runs as the compiled Pallas kernel.
   Every task must end DONE (none lost, duplicated or errored), and on a
   subset of each batch the knotted ids and their writhe must match the
   plain ``jnp`` reference (``kernels/ref.py``).
2. **serving** — ``stablelm_1_6b`` at its published widths (24 layers,
   d_model 2048, 32 heads of 64, vocab 100352, bf16, random weights from the
   seed) through ``ServeEngine(paged=True)`` with the Pallas paged
   flash-decode kernel, 8 requests of mixed prompt lengths, 32 new tokens
   each, stepped in lockstep with a second engine on the XLA
   ``chunked_attention`` path: per-step logits must agree within
   ``LOGIT_RTOL`` and greedy tokens until a near-tie.

Four chips (``--chips 4``), and nothing else:

3. **sharded step** — ``make_train_step`` of smoke configs (the MoE island
   of ``moonlight_16b_a3b``, vocab-parallel CE) on a (data=2, model=2)
   mesh of the four chips against the same step on one chip;
4. **replicas** — ``ServeReplicaSet`` of 4 ``stablelm_1_6b`` replicas, each
   holding its params and caches on its own chip, against one engine.

There is no CPU mode: where JAX finds no TPU the script exits non-zero
before any phase. Wall times it prints include compilation and are set-up
figures, not measurements. The last line of stdout is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from collections import deque
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SEED = 0

# phase 1: knot campaign
KNOT_STRUCTURES = 12_000
KNOT_BATCH = 4000            # structures per task (paper §4)
KNOT_POINTS = 256
KNOT_REF_SUBSET = 256        # ids per batch re-checked against the reference
WRITHE_ATOL = 1e-2           # |Wr(kernel) - Wr(reference)| per structure

# phase 2: serving
SERVE_ARCH = "stablelm_1_6b"
SERVE_SLOTS = 8
SERVE_MAX_LEN = 1024
SERVE_PROMPT_LENS = (16, 400, 37, 256, 64, 311, 128, 190)
SERVE_NEW_TOKENS = 32
# bf16 model, two attention lowerings: per slot and step,
# max|logit_flash - logit_ref| <= LOGIT_RTOL * max|logit_ref|
LOGIT_RTOL = 0.05

# phase 3: sharded step
SHARDED_ARCHS = ("moonlight_16b_a3b", "stablelm_1_6b")
LOSS_RTOL = 2e-3
UPDATE_RTOL = 1e-2

# phase 4: replicas
REPLICAS = 4
REPLICA_PROMPT_LENS = tuple(16 + 8 * i for i in range(16))


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# phase 1: knot campaign
# ---------------------------------------------------------------------------


def _reference_writhe(ids: list[int], n_points: int,
                      chunk: int = 64) -> dict[int, float]:
    """Total writhe per id from the plain jnp writhe map."""
    import jax.numpy as jnp
    import numpy as np

    from repro.apps import knots
    from repro.kernels.ref import writhe_map_ref

    out: dict[int, float] = {}
    for i in range(0, len(ids), chunk):
        part = ids[i:i + chunk]
        coords, _ = knots.synthesize_batch(part, n_points)
        w = writhe_map_ref(jnp.asarray(coords))
        wr = np.asarray(w.sum(axis=(1, 2)) / 2.0)
        out.update(zip(part, (float(x) for x in wr)))
    return out


def knot_phase(n_structures: int = KNOT_STRUCTURES,
               batch_size: int = KNOT_BATCH, n_points: int = KNOT_POINTS,
               ref_subset: int = KNOT_REF_SUBSET) -> dict:
    """Run the campaign and check it; returns counts for the log."""
    from repro.apps import knots
    from repro.cluster import KsaCluster
    from repro.core.scheduling import ResourceProfile

    spec = knots.knots_pipeline(batch_size, n_points=n_points)
    ids = list(range(n_structures))
    localize_mb = spec.stages["localize"].resources.mem_mb
    t0 = time.time()
    with KsaCluster(prefix="smoke") as c:
        # one slot: the chip runs one batch at a time, and a task's memory
        # is charged as process RSS growth, which a concurrent task's
        # allocations would blur
        c.add_worker(slots=1, profile=ResourceProfile(
            cpus=2, mem_mb=localize_mb))
        res = c.run_campaign(spec, ids, timeout_s=1200.0)
    wall = time.time() - t0

    st = res.status
    check(st.state == "COMPLETED", f"campaign ended {st.state}: {st.failure}")
    for name, s in st.stages.items():
        check(s.errors == 0 and s.failed == 0,
              f"stage {name}: {s.errors} task errors, {s.failed} failed")
        check(s.duplicates == 0, f"stage {name}: {s.duplicates} duplicates")
        check(s.retried == 0 and s.revoked == 0,
              f"stage {name}: {s.retried} retries, {s.revoked} revocations")
        check(s.done + s.skipped == s.expected,
              f"stage {name}: {s.done} done + {s.skipped} skipped of "
              f"{s.expected} expected (tasks lost)")
    agg = res.final
    n_batches = math.ceil(n_structures / batch_size)
    check(agg["processed"] == n_structures and agg["batches"] == n_batches,
          f"aggregate saw {agg['processed']} structures in "
          f"{agg['batches']} batches, expected {n_structures} in {n_batches}")

    screens = res.results["screen"]
    checked = borderline = 0
    worst = 0.0
    for k, r in enumerate(screens):
        batch = ids[k * batch_size:(k + 1) * batch_size]
        check(r["processed"] == len(batch), f"batch {k}: processed "
              f"{r['processed']} of {len(batch)}")
        q = knots.quality_score(batch)
        kept = [i for i, qi in zip(batch, q) if qi >= knots.QUALITY_THRESHOLD]
        check(r["kept"] == len(kept), f"batch {k}: kept {r['kept']}, "
              f"quality filter keeps {len(kept)}")
        knotted = set(r["knotted"])
        check(knotted <= set(kept), f"batch {k}: knotted ids not kept")
        step = max(1, len(kept) // ref_subset)
        subset = kept[::step][:ref_subset]
        ref = _reference_writhe(subset, n_points)
        for i in subset:
            ref_knotted = abs(ref[i]) >= knots.WRITHE_KNOT_THRESHOLD
            if abs(abs(ref[i]) - knots.WRITHE_KNOT_THRESHOLD) <= WRITHE_ATOL:
                borderline += 1      # either verdict is within tolerance
            else:
                check((i in knotted) == ref_knotted,
                      f"structure {i}: kernel knotted={i in knotted}, "
                      f"reference Wr={ref[i]:.4f}")
            if i in knotted:
                err = abs(r["wr"][str(i)] - ref[i])
                worst = max(worst, err)
                check(err <= WRITHE_ATOL, f"structure {i}: Wr kernel "
                      f"{r['wr'][str(i)]:.5f} vs reference {ref[i]:.5f}")
            checked += 1
    check(sum(len(r["knotted"]) for r in screens) == len(agg["knotted"]),
          "a knotted id was reported twice")
    return {"structures": n_structures, "batches": n_batches,
            "kept": agg["kept"], "knotted": len(agg["knotted"]),
            "cores": len(agg["cores"]), "ref_checked": checked,
            "ref_borderline": borderline, "worst_wr_err": worst,
            "wall_s": wall}


# ---------------------------------------------------------------------------
# phase 2: serving at full width, flash-decode vs chunked attention
# ---------------------------------------------------------------------------


def _init_params(cfg, seed: int):
    """Random weights from the seed, as one jitted program (eager
    ``init_params`` compiles an op per leaf shape: about a minute for
    stablelm-1.6b on one v5e chip)."""
    import jax
    import jax.numpy as jnp

    from repro.models import init_params, model_spec

    spec, dtype = model_spec(cfg), jnp.dtype(cfg.dtype)
    params = jax.jit(lambda key: init_params(spec, key, dtype))(
        jax.random.PRNGKey(seed))
    return jax.block_until_ready(params)


def _requests(cfg, prompt_lens, max_new: int, seed: int) -> list:
    import numpy as np
    rng = np.random.RandomState(seed)
    return [(f"req{i}", rng.randint(0, cfg.vocab_size, n).tolist(), max_new)
            for i, n in enumerate(prompt_lens)]


def _logit_stats(vocab: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def stats(got, ref):
        got = got[:, :vocab].astype(jnp.float32)
        ref = ref[:, :vocab].astype(jnp.float32)
        top2 = jax.lax.top_k(ref, 2)[0]
        return (jnp.max(jnp.abs(got - ref), axis=1),
                jnp.max(jnp.abs(ref), axis=1),
                top2[:, 0] - top2[:, 1])
    return stats


def serve_phase(cfg=None, *, n_slots: int = SERVE_SLOTS,
                max_len: int = SERVE_MAX_LEN,
                prompt_lens=SERVE_PROMPT_LENS,
                max_new: int = SERVE_NEW_TOKENS, seed: int = SEED,
                interpret: bool = False) -> dict:
    """Two engines over the same requests, stepped in lockstep: paged
    Pallas flash-decode vs dense XLA chunked attention. ``interpret`` only
    matters off TPU (CPU rehearsals of this phase)."""
    import numpy as np

    from repro.configs import get_config
    from repro.serve import ServeEngine

    cfg = cfg or get_config(SERVE_ARCH)
    t0 = time.time()
    params = _init_params(cfg, seed)
    t_init = time.time() - t0
    reqs = _requests(cfg, prompt_lens, max_new, seed)
    flash = ServeEngine(cfg, params, n_slots=n_slots, max_len=max_len,
                        paged=True, decode_kernel="flash",
                        kernel_interpret=interpret)
    ref = ServeEngine(cfg, params, n_slots=n_slots, max_len=max_len,
                      decode_kernel="chunked")
    check(flash.step_latency_s == 0.0, "emulated step latency is on")
    stats = _logit_stats(cfg.vocab_size)

    queue = deque(reqs)
    out_flash: dict[str, list[int]] = {}
    out_ref: dict[str, list[int]] = {}
    parted: set[str] = set()   # requests whose greedy tokens parted at a tie
    worst = 0.0
    compared = steps = 0
    t_first = None
    t0 = time.time()
    while queue or flash._active():
        while queue and flash.add_request(*queue[0]):
            check(ref.add_request(*queue[0]),
                  "the reference engine refused an admission")
            queue.popleft()
        live = {i: (s.request_id, s.position, len(s.prompt))
                for i, s in enumerate(flash.slots) if not s.done}
        check(live == {i: (s.request_id, s.position, len(s.prompt))
                       for i, s in enumerate(ref.slots) if not s.done},
              f"step {steps}: the engines' slots differ")
        for rid, toks in flash.step():
            out_flash[rid] = toks
        for rid, toks in ref.step():
            out_ref[rid] = toks
        if t_first is None:
            t_first = time.time() - t0
        diff, scale, gap = (np.asarray(a) for a in
                            stats(flash.last_logits, ref.last_logits))
        for i, (rid, pos, plen) in live.items():
            if rid in parted:
                continue
            tol = LOGIT_RTOL * scale[i]
            worst = max(worst, float(diff[i] / scale[i]))
            compared += 1
            check(diff[i] <= tol, f"{rid} at position {pos}: logits differ "
                  f"by {diff[i]:.4g} > {tol:.4g}")
            if pos + 1 < plen:
                continue                  # still feeding the prompt
            tf, tr = flash.slots[i].tokens[-1], ref.slots[i].tokens[-1]
            if tf != tr:
                check(gap[i] <= tol, f"{rid} at position {pos}: greedy "
                      f"tokens {tf} != {tr} with top-2 gap {gap[i]:.4g} "
                      f"> {tol:.4g}")
                parted.add(rid)
        steps += 1
    wall = time.time() - t0
    check(set(out_flash) == set(out_ref) == {r[0] for r in reqs},
          "not every request was answered")
    for rid, _, n in reqs:
        check(len(out_flash[rid]) == n, f"{rid}: {len(out_flash[rid])} "
              f"tokens, asked for {n}")
        if rid not in parted:
            check(out_flash[rid] == out_ref[rid], f"{rid}: tokens differ")
    return {"requests": len(reqs), "tokens": flash.tokens_out,
            "steps": steps, "compared": compared, "parted_at_tie":
            len(parted), "worst_logit_rel": worst, "params_init_s": t_init,
            "first_step_s": t_first, "wall_s": wall}


# ---------------------------------------------------------------------------
# four chips: sharded train step, replicas on their own chips
# ---------------------------------------------------------------------------


def sharded_phase(archs=SHARDED_ARCHS) -> list[dict]:
    from repro.launch.mesh import make_smoke_mesh
    from repro.sharding.parity import train_step_parity

    mesh = make_smoke_mesh(n_data=2, n_model=2)
    out = []
    for arch in archs:
        r = train_step_parity(arch, mesh)
        check(r["loss_rel"] <= LOSS_RTOL and r["update_rel"] <= UPDATE_RTOL,
              f"{arch}: sharded step differs: {r}")
        out.append(r)
    return out


def replica_phase(cfg=None, *, n_replicas: int = REPLICAS,
                  n_slots: int = SERVE_SLOTS, max_len: int = SERVE_MAX_LEN,
                  prompt_lens=REPLICA_PROMPT_LENS,
                  max_new: int = SERVE_NEW_TOKENS, seed: int = SEED,
                  interpret: bool = False) -> dict:
    """Replica i serves from devices[i]; every request's tokens must equal
    those of a single engine on devices[0]."""
    import jax

    from repro.configs import get_config
    from repro.serve import ServeEngine, ServeReplicaSet

    cfg = cfg or get_config(SERVE_ARCH)
    devices = jax.devices()[:n_replicas]
    check(len(devices) == n_replicas,
          f"{n_replicas} replicas need as many devices, found {len(devices)}")
    params = _init_params(cfg, seed)
    reqs = _requests(cfg, prompt_lens, max_new, seed)
    kw = dict(n_slots=n_slots, max_len=max_len, paged=True,
              decode_kernel="flash", kernel_interpret=interpret)
    t0 = time.time()
    single = ServeEngine(cfg, params, device=devices[0], **kw)
    want = single.run_until_drained(list(reqs))
    t_single = time.time() - t0
    del single          # frees its KV pool on devices[0] before replica 0's

    rs = ServeReplicaSet(cfg, params, n_replicas=n_replicas, engine_kw=kw)

    def placed() -> None:
        for i, eng in enumerate(rs.engines):
            for leaf in jax.tree.leaves((eng.params, eng.caches)):
                check(leaf.devices() == {devices[i]},
                      f"replica {i} holds an array on {leaf.devices()}")

    placed()
    pending = [rs.submit(rid, prompt, n) for rid, prompt, n in reqs]
    t0 = time.time()
    with rs:
        check(rs.drain(timeout=900.0), "replicas did not drain")
    t_replicas = time.time() - t0
    placed()
    for p in pending:
        check(p.status == "done", f"{p.request_id}: {p.status}")
        check(p.tokens == want[p.request_id],
              f"{p.request_id} on replica {p.replica} differs from the "
              "single engine")
    served_by = sorted({p.replica for p in pending})
    check(served_by == list(range(n_replicas)),
          f"requests reached only replicas {served_by}")
    check(rs.completed == len(reqs) and rs.duplicates == 0,
          f"{rs.completed} completed, {rs.duplicates} duplicates")
    return {"replicas": n_replicas, "requests": len(reqs),
            "tokens": sum(len(p.tokens) for p in pending),
            "single_engine_s": t_single, "replica_set_s": t_replicas}


# ---------------------------------------------------------------------------


class _CompileClock:
    """Backend compiles (count, seconds), from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.secs = 0.0

    def __call__(self, event: str, duration_secs: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1
            self.secs += duration_secs


def _peak_bytes(devices) -> list:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: knot campaign + serving; 4: sharded step + "
                         "replicas, nothing else")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    log(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{enable_compile_cache()}")
    clock = _CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        if args.chips == 1:
            phases = [("knot_campaign", knot_phase),
                      ("serve_flash_vs_chunked", serve_phase)]
        else:
            phases = [("sharded_train_step", sharded_phase),
                      ("replicas", replica_phase)]
        for name, fn in phases:
            t0, n0, c0 = time.time(), clock.count, clock.secs
            result = fn()
            log(f"[{name}] ok in {time.time() - t0:.1f}s set-up wall time, "
                f"including {clock.count - n0} backend compiles taking "
                f"{clock.secs - c0:.1f}s: {result}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"peak bytes in use per device: {_peak_bytes(devices)}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
