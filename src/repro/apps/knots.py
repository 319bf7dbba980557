"""Knot detection over predicted protein structures — the paper's workload
(§4), scaled to this container.

Pipeline mirrors AlphaKnot 2.0:

1. generate/ingest backbone traces (synthetic here: knotted families — the
   trefoil/figure-8 harmonic embeddings that Topoly uses as references — vs
   unknotted random coils; pLDDT-style quality filtering is emulated with a
   per-structure quality score),
2. **stage 1 screen**: total writhe + average crossing number (ACN) from the
   Gauss-linking writhe map (the compiled Pallas kernel on TPU, the jnp
   reference elsewhere; see ``repro.kernels.ops``) — the fast invariant,
   analogous to the paper's HOMFLY-PT screen with 200 random closures,
3. **stage 2 knot-core localization** for candidates passing the screen: the
   paper's subchain heuristic — slide (a, b) windows over the writhe map and
   find the smallest subchain whose |writhe| stays above threshold (the
   "knot core" that distinguishes deep from shallow knots).

Everything is batched (B, n_points, 3) and runs as KSA tasks in batches of
``batch_size`` structures (paper: 4000/task).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ClusterComputing, register_script
from repro.kernels import ops as kops
from repro.obs import phase

WRITHE_KNOT_THRESHOLD = 2.5   # |Wr| above this ⇒ knot candidate
QUALITY_THRESHOLD = 0.70      # emulated pLDDT cut (paper: 70)


# ---------------------------------------------------------------------------
# synthetic structure generation
# ---------------------------------------------------------------------------

def torus_knot(p: int, q: int, n: int, scale: float = 1.0,
               noise: float = 0.0, seed: int = 0) -> np.ndarray:
    """(p, q) torus-knot backbone with n residues (3_1 = (2,3), 5_1 = (2,5))."""
    t = np.linspace(0, 2 * np.pi, n, endpoint=False)
    r = np.cos(q * t) + 2.0
    pts = np.stack([r * np.cos(p * t), r * np.sin(p * t),
                    -np.sin(q * t)], -1) * scale
    if noise:
        pts = pts + np.random.RandomState(seed).randn(n, 3) * noise
    return pts.astype(np.float32)


def figure8(n: int, noise: float = 0.0, seed: int = 0) -> np.ndarray:
    t = np.linspace(0, 2 * np.pi, n, endpoint=False)
    pts = np.stack([
        (2 + np.cos(2 * t)) * np.cos(3 * t),
        (2 + np.cos(2 * t)) * np.sin(3 * t),
        np.sin(4 * t)], -1)
    if noise:
        pts = pts + np.random.RandomState(seed).randn(n, 3) * noise
    return pts.astype(np.float32)


def random_coil(n: int, seed: int = 0,
                drift: tuple[float, float, float] = (1.0, 0.0, 0.0)
                ) -> np.ndarray:
    """Extended random coil: a drift term keeps the open chain from
    collapsing into a geometrically-entangled globule (unbiased walks often
    carry |Wr| > 3 — real, but noise for a screening benchmark)."""
    rng = np.random.RandomState(seed)
    steps = rng.randn(n, 3)
    steps /= np.linalg.norm(steps, axis=1, keepdims=True)
    steps = steps + np.asarray(drift)
    return np.cumsum(steps * 1.2, axis=0).astype(np.float32)


def deep_knot(n: int, core: int = 80, seed: int = 0) -> np.ndarray:
    """A trefoil core embedded mid-chain between two *extended* tails — the
    paper's 'deep knot' (Taylor 2000): trimming the tails keeps the knot.

    The torus-knot cut leaves both endpoints adjacent in space, so both tails
    must exit on the same side (radially outward) — ends that wander back
    through the loop would untie the open chain, which is exactly the
    shallow-knot failure mode the deep/shallow distinction is about."""
    tre = torus_knot(2, 3, core, scale=1.2, noise=0.03, seed=seed)
    center = tre.mean(0)
    d = tre[0] - center
    d = d / (np.linalg.norm(d) + 1e-9) * 5.0
    tail = (n - core) // 2
    head = random_coil(tail, seed + 1, drift=tuple(d)) + tre[0]
    foot = random_coil(n - core - tail, seed + 2, drift=tuple(d)) + tre[-1]
    return np.concatenate([head[::-1], tre, foot], 0).astype(np.float32)


def synthesize_batch(ids: list[int], n_points: int = 128) -> tuple[np.ndarray, list[str]]:
    """Deterministic mixed population keyed by structure id.

    Note: the figure-8 knot is amphichiral (Wr ≈ 0) and *invisible* to a
    writhe screen — exactly why the paper's pipeline computes HOMFLY-PT.
    The population here uses chiral knots (3_1, 5_1); the figure-8
    limitation is asserted explicitly in tests/test_knots.py."""
    out, truth = [], []
    for i in ids:
        kind = i % 4
        if kind == 0:
            out.append(torus_knot(2, 3, n_points, noise=0.05, seed=i))
            truth.append("trefoil")
        elif kind == 1:
            out.append(random_coil(n_points, seed=i))
            truth.append("unknot")
        elif kind == 2:
            out.append(torus_knot(2, 5, n_points, noise=0.05, seed=i))
            truth.append("cinquefoil")
        else:
            out.append(deep_knot(n_points, core=max(n_points // 2, 48),
                                 seed=i))
            truth.append("deep_trefoil")
    return np.stack(out), truth


def quality_score(ids: list[int]) -> np.ndarray:
    """Emulated pLDDT, uniform in [0.4, 1.0) (deterministic per id). About
    half of the structures fall below the 0.70 cut; the paper's screen
    dropped 54M of 214M."""
    rng = np.random.RandomState(12345)
    all_q = 0.4 + 0.6 * rng.random(10_000_000)
    return np.array([all_q[i % len(all_q)] for i in ids], np.float32)


# ---------------------------------------------------------------------------
# invariants
# ---------------------------------------------------------------------------

def writhe_and_acn(coords: jax.Array
                   ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """-> (total writhe (B,), ACN (B,), writhe map (B, n, n))."""
    w = kops.writhe(coords)
    wr = w.sum(axis=(1, 2)) / 2.0
    acn = jnp.abs(w).sum(axis=(1, 2)) / 2.0
    return wr, acn, w


def knot_core(wmap: np.ndarray, threshold: float = WRITHE_KNOT_THRESHOLD,
              min_len: int = 16, check_cancel=None) -> tuple[int, int] | None:
    """Knot-core localization (paper §4: the subchain heuristic replacing
    the O(n²)-subchain Alexander knot map at AlphaFold scale).

    Shrinks [a, b) greedily from both ends while |writhe(subchain)| stays
    above threshold; O(n) evaluations over the precomputed map's prefix
    sums instead of O(n²) invariant computations. ``check_cancel`` is
    called once per shrink step — the O(chain-length) loop here is where a
    long localization actually spends its time, so a revoked lease must be
    observed *inside* it, not only between structures."""
    n = wmap.shape[0]
    # 2D prefix sums for O(1) subchain writhe
    ps = np.zeros((n + 1, n + 1))
    ps[1:, 1:] = np.cumsum(np.cumsum(wmap, 0), 1)

    def sub_writhe(a: int, b: int) -> float:
        return (ps[b, b] - ps[a, b] - ps[b, a] + ps[a, a]) / 2.0

    a, b = 0, n
    if abs(sub_writhe(a, b)) < threshold:
        return None
    changed = True
    while changed and b - a > min_len:
        if check_cancel is not None:
            check_cancel()
        changed = False
        if abs(sub_writhe(a + 1, b)) >= threshold:
            a += 1
            changed = True
        if b - a > min_len and abs(sub_writhe(a, b - 1)) >= threshold:
            b -= 1
            changed = True
    return (a, b)


def classify(wr: float) -> str:
    if abs(wr) < WRITHE_KNOT_THRESHOLD:
        return "unknot"
    return "knotted"


# ---------------------------------------------------------------------------
# the KSA task (paper Fig. 3 pattern)
# ---------------------------------------------------------------------------

def _screen_batch(ids: list[int], n_points: int
                  ) -> tuple[list[int], list[int], dict[str, float], float]:
    """Quality-filter + writhe/ACN screen one batch of structure ids.
    -> (kept_ids, knotted_ids, writhe per knotted id, mean ACN over kept).

    Its phases (``knot.quality``, ``knot.synthesize``, ``knot.writhe``,
    ``knot.select``) split the screen task's run; ``knot.writhe`` ends
    where the host waits for the totals."""
    with phase("knot.quality"):
        q = quality_score(ids)
        keep = q >= QUALITY_THRESHOLD
        kept_ids = [i for i, k in zip(ids, keep) if k]
    if not kept_ids:
        return [], [], {}, 0.0
    with phase("knot.synthesize"):
        coords, _ = synthesize_batch(kept_ids, n_points)
    with phase("knot.writhe"):
        wr, acn, _ = writhe_and_acn(jnp.asarray(coords))
        wr = np.asarray(wr)
    with phase("knot.select"):
        knotted = [int(i) for i, w in zip(kept_ids, wr)
                   if abs(float(w)) >= WRITHE_KNOT_THRESHOLD]
        wr_by_id = {str(i): float(w) for i, w in zip(kept_ids, wr)
                    if int(i) in set(knotted)}
        mean_acn = float(np.asarray(acn).mean())
    return kept_ids, knotted, wr_by_id, mean_acn


def _localize_cores(survivors: list[int], n_points: int,
                    check_cancel) -> dict[str, list[int]]:
    """Knot-core localization for screen survivors. Shared by the flat
    ``knot_batch`` task and the pipeline ``knot_localize`` stage so the two
    paths cannot drift apart (flat-vs-campaign parity is asserted in tests
    and examples).

    ``check_cancel`` is required and called unconditionally in every
    O(chain-length) loop (here per structure, and inside each
    :func:`knot_core` shrink loop): a revoked lease
    (``Broker.revoke_lease`` — watchdog, preemption, drain, scancel) stops
    the task promptly instead of after the whole batch. Its phases are
    ``knot.synthesize``, ``knot.writhe`` (through the map's copy to the
    host) and ``knot.core``."""
    cores: dict[str, list[int]] = {}
    if not survivors:
        return cores
    with phase("knot.synthesize"):
        coords, _ = synthesize_batch(survivors, n_points)
    with phase("knot.writhe"):
        _, _, wmap = writhe_and_acn(jnp.asarray(coords))
        wmap_np = np.asarray(wmap)
    with phase("knot.core"):
        for k, i in enumerate(survivors):
            check_cancel()
            core = knot_core(wmap_np[k], check_cancel=check_cancel)
            if core is not None:
                cores[str(i)] = list(core)
    return cores


@register_script("knot_batch")
class KnotBatchComputing(ClusterComputing):
    """params: batch (list of structure ids), n_points, stage2 (bool).
    One task = one batch of structures (paper: 4000/batch).
    Flat single-stage baseline: screen + localize fused in one task, built
    on the same helpers the pipeline stages use."""

    def run(self) -> Any:
        ids = list(self.params["batch"])
        n_points = int(self.params.get("n_points", 128))
        stage2 = bool(self.params.get("stage2", True))

        kept_ids, knotted, _, mean_acn = _screen_batch(ids, n_points)
        self.send_status("RUNNING", stage="screen", kept=len(kept_ids),
                         dropped=len(ids) - len(kept_ids))
        self.check_cancel()

        cores: dict[str, list[int]] = {}
        if stage2 and knotted:
            self.send_status("RUNNING", stage="knot_core",
                             candidates=len(knotted))
            cores = _localize_cores(knotted, n_points, self.check_cancel)
        return {
            "processed": len(ids),
            "kept": len(kept_ids),
            "knotted": knotted,
            "cores": cores,
            "mean_acn": mean_acn,
        }


# ---------------------------------------------------------------------------
# the campaign as a 3-stage DAG (repro.pipeline)
# ---------------------------------------------------------------------------
#
# The same workload as ``knot_batch``, decomposed the way the paper's
# production deployment is (§4): a cheap screening stage fans out over
# batches, an expensive localization stage runs only on the survivors, and a
# join barrier aggregates the campaign. Stage results are numerically
# identical to the flat baseline because structure synthesis is deterministic
# per id.

@register_script("knot_screen")
class KnotScreenComputing(ClusterComputing):
    """Stage 1 (source, fan-out): generate + quality-filter + writhe/ACN
    screen one batch. params: batch (ids), n_points."""

    def run(self) -> Any:
        ids = list(self.params["batch"])
        n_points = int(self.params.get("n_points", 128))
        kept_ids, knotted, wr_by_id, mean_acn = _screen_batch(ids, n_points)
        self.send_status("RUNNING", stage="screen", kept=len(kept_ids),
                         survivors=len(knotted))
        self.check_cancel()
        return {
            "processed": len(ids),
            "kept": len(kept_ids),
            "knotted": knotted,
            "wr": wr_by_id,
            "mean_acn": mean_acn,
        }


@register_script("knot_localize")
class KnotLocalizeComputing(ClusterComputing):
    """Stage 2 (map, 1:1 with screen tasks): knot-core localization on the
    survivors of one screen batch. The upstream screen result arrives as
    ``params["upstream"]``; coordinates are re-synthesized for survivors only
    (the paper ships structures via shared storage, not the broker)."""

    def run(self) -> Any:
        upstream = dict(self.params.get("upstream") or {})
        survivors = [int(i) for i in upstream.get("knotted", [])]
        n_points = int(self.params.get("n_points", 128))
        cores = _localize_cores(survivors, n_points, self.check_cancel)
        return {"candidates": len(survivors), "cores": cores}


@register_script("knot_aggregate")
class KnotAggregateComputing(ClusterComputing):
    """Stage 3 (join barrier): aggregate every screen + localize result into
    the campaign-level report. Fires exactly once per campaign."""

    def run(self) -> Any:
        upstream = dict(self.params.get("upstream") or {})
        screens = [r for r in upstream.get("screen", []) if r]
        locs = [r for r in upstream.get("localize", []) if r]
        processed = sum(int(r.get("processed", 0)) for r in screens)
        kept = sum(int(r.get("kept", 0)) for r in screens)
        knotted = sorted({int(i) for r in screens
                          for i in r.get("knotted", [])})
        cores: dict[str, list[int]] = {}
        for r in locs:
            cores.update(r.get("cores", {}))
        acn_num = sum(float(r.get("mean_acn", 0.0)) * int(r.get("kept", 0))
                      for r in screens)
        return {
            "processed": processed,
            "kept": kept,
            "knotted": knotted,
            "cores": cores,
            "mean_acn": acn_num / kept if kept else 0.0,
            "batches": len(screens),
        }


def _no_survivors(screen_result: Any) -> bool:
    """Conditional-edge predicate: a screen batch with no knot candidates has
    nothing to localize."""
    return not screen_result.get("knotted")


def knots_pipeline(batch_size: int = 12, *, n_points: int = 96,
                   max_in_flight: int | None = None,
                   max_attempts: int = 4,
                   task_timeout_s: float | None = None,
                   skip_empty: bool = True,
                   gpu_localize: bool = False,
                   localize_site: str = ""):
    """The AlphaKnot campaign as a declarative 3-stage DAG:
    screen (fan-out) → localize (map over survivors) → aggregate (join).

    Screen runs on cheap 1-CPU slots; localize requests more CPU (the
    heterogeneous-stage routing of ParaFold: different resource profiles per
    stage) — or, with ``gpu_localize``, a GPU: the writhe-map localization is
    the kernel-heavy stage, and requesting ``gpus=1`` routes it to the GPU
    class topic so only GPU pools (static or autoscaled) serve it, exactly
    ParaFold's CPU-featurize/GPU-predict split. Aggregate is a single
    barrier task. With ``skip_empty`` (default) localize tasks are *skipped*
    for screen batches with zero survivors — the ROADMAP's conditional-edge
    early exit; the campaign still completes, and the aggregate sees one
    result per non-empty batch.

    Under a :class:`~repro.federation.FederatedCluster`, ``localize_site``
    pins the kernel-heavy stage to a named federation site
    (``Resources.site`` affinity — e.g. the big remote HPC pool) while
    screen and aggregate stay site-free and run home or spill."""
    from repro.pipeline import PipelineSpec, RetryPolicy, Stage
    from repro.core import Resources

    retry = RetryPolicy(max_attempts=max_attempts, timeout_s=task_timeout_s)
    common = {"n_points": n_points}
    # localization pulls a whole batch's f32 writhe map to the host: at the
    # paper's 4000 structures x 256 points that alone is ~1 GB, past the
    # 1 GB default request that memory policing enforces.
    map_mb = math.ceil(batch_size * (n_points - 1) ** 2 * 4 / 2**20)
    mem_mb = max(Resources().mem_mb, 512 + map_mb)
    localize_res = (Resources(cpus=1, gpus=1, mem_mb=mem_mb) if gpu_localize
                    else Resources(cpus=2, mem_mb=mem_mb))
    localize_res.site = localize_site
    return PipelineSpec("alphaknot", [
        Stage("screen", "knot_screen", fan_out=batch_size, params=common,
              resources=Resources(cpus=1), max_in_flight=max_in_flight,
              retry=retry),
        Stage("localize", "knot_localize", depends_on=("screen",),
              params=common, resources=localize_res,
              max_in_flight=max_in_flight, retry=retry,
              skip_when=_no_survivors if skip_empty else None),
        Stage("aggregate", "knot_aggregate",
              depends_on=("screen", "localize"), join=True, retry=retry),
    ])
