"""Driver for a served decoder model: ``ServeReplicaSet`` replicas deployed
as KSA tasks on a ``serve``-tainted pool of a ``KsaCluster``, driven by one
client loop in this process.

Set-up makes the weights on the device from the seed (one jitted call of
the configuration's reference ``init_weights``), deploys the replicas,
serves one request to compile the step, and runs ``lead_in_s`` of the
mix's own load so the window starts in steady state. The mix is a closed
loop: ``outstanding_per_slot`` requests per slot kept outstanding. The
window opens at a completion and closes at the first completion
``seconds`` later; ``output_tokens_per_s`` is the output tokens of the
requests completed in between over its length.

Afterwards a sample of finished requests (the longest among them) is run
through the float32 reference, and ``served_gap`` is the widest gap by
which a served token's reference logit lies below the reference's best.
"""
from __future__ import annotations

import gc
import time
from contextlib import contextmanager

import numpy as np

import loadgen
import stats
import tracing
from harness import Run, log, peak_bytes

POLL_S = 0.01
MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab_size", "rope_theta", "rms_eps", "dtype")


def model_config(c: dict):
    """The program's config for this file: its named architecture with
    every size the file states."""
    from repro.configs import get_config
    return get_config(c["arch"]).with_(**{k: c[k] for k in MODEL_KEYS})


def seed_key(seed: int):
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


class Hist:
    """A window's view of one serve histogram child: the change in its
    count and sum."""

    def __init__(self, child):
        self.child = child
        self.count0, self.sum0 = child.count, child.sum

    def close(self) -> dict:
        return {"count": self.child.count - self.count0,
                "sum": self.child.sum - self.sum0}


def make_weights(r: Run):
    """The seed's weights, on the device, from one jitted call."""
    import jax
    c = r.config
    return jax.block_until_ready(jax.jit(
        lambda k: r.reference.init_weights(k, c))(seed_key(r.seed)))


@contextmanager
def deployed(r: Run, weights):
    """A ``KsaCluster`` with the configuration's replicas deployed on its
    ``serve`` pool and their step compiled by one request. Yields the
    replica set and replica 0's serve metric children."""
    from repro.cluster import KsaCluster
    from repro.core.scheduling import ResourceClassPolicy
    from repro.serve import ServeReplicaSet, register_serve_metrics

    c, sv = r.config, r.config["serving"]
    with KsaCluster(prefix="bench", placement=ResourceClassPolicy(
            extra_classes=("serve",))) as cluster:
        rs = ServeReplicaSet(model_config(c),
                             r.reference.program_params(weights),
                             n_replicas=int(sv["n_replicas"]),
                             engine_kw=dict(n_slots=int(sv["n_slots"]),
                                            max_len=int(sv["max_len"]),
                                            paged=True,
                                            page_size=int(sv["page_size"]),
                                            decode_kernel=sv["decode_kernel"]),
                             registry=cluster.broker.metrics)
        rs.deploy(cluster, taint="serve")
        try:
            warm = rs.submit("warm", [1, 2, 3], max_new=2)
            if not warm.wait(float(r.mix["setup_timeout_s"])):
                raise TimeoutError("the warm-up request did not finish")
            fams = register_serve_metrics(cluster.broker.metrics)
            yield rs, {n: fams[n].labels(replica="r0")
                       for n in ("step", "slots_active", "pages_used")}
        finally:
            rs.stop()


def run(r: Run) -> None:
    import jax

    c, mix, ref = r.config, r.mix, r.reference
    sv = c["serving"]
    weights = make_weights(r)
    capture = tracing.Capture(r.out_dir / "trace") if r.trace else None
    vocab = int(c["vocab_size"])
    finished: dict[str, tuple] = {}      # rid -> (request, tokens, t_done)
    gauges: list[tuple] = []             # (t, slots_active, pages_used)

    with deployed(r, weights) as (rs, child):
        def sample(now):
            gauges.append((now, child["slots_active"].value,
                           child["pages_used"].value))

        out = _closed(r, rs, mix, vocab, child, sample, capture, finished)
    r.memory_peak_bytes = peak_bytes(jax.devices())
    del rs
    gc.collect()

    r.readings.update(out["readings"])
    r.readings.update(
        gauges=gauges, device_kind=jax.devices()[0].device_kind,
        model=c, page_size=int(sv["page_size"]), n_slots=int(sv["n_slots"]))
    if capture is not None:
        tr = capture.load()
        r.readings["trace"] = tr
    pick = _sample(finished, out["checkable"], int(mix["check_requests"]),
                   r.seed)
    items = [finished[rid] for rid in pick]
    r.evidence.update(weights=weights, items=items)
    gap = served_gap(ref, weights, c, items)
    log(f"checked {len(pick)} requests, "
        f"{sum(len(finished[p][1]) for p in pick)} served tokens")
    r.check("served_gap", gap, c["limits"]["served_gap"])


def _closed(r, rs, mix, vocab, child, sample, capture, finished) -> dict:
    slots = int(r.config["serving"]["n_slots"])
    seq = iter(loadgen.closed_loop(mix, r.seed, vocab, stagger=slots))
    target = int(mix["outstanding_per_slot"]) * slots
    pending: dict[str, tuple] = {}
    edges: list[tuple[float, float]] = []

    def submit():
        q = next(seq)
        pending[q.rid] = (rs.submit(q.rid, q.prompt, q.max_new), q)

    for _ in range(target):
        submit()
    t_lead_end = time.time() + float(mix["lead_in_s"])
    t_open = window = hists = None
    while window is None:
        now = time.time()
        for rid in [k for k, v in pending.items() if v[0].resolved]:
            p, q = pending.pop(rid)
            if p.status != "done":
                raise RuntimeError(f"request {rid} ended {p.status}")
            finished[rid] = (q, p.tokens, now)
            if now >= t_lead_end:
                edges.append((now, float(len(p.tokens))))
            submit()
        sample(now)
        if t_open is None and edges:
            t_open = edges[0][0]
            r.setup_done()
            r.compiles.counting = True
            hists = {"step": Hist(child["step"])}
        if t_open is not None:
            if capture is not None and capture.wall_open is None and \
                    now >= t_open + r.seconds - float(mix["trace_s"]):
                capture.start()
            window = stats.rate_between_edges(edges, t_open, r.seconds)
        if now - t_lead_end > r.seconds + float(mix["setup_timeout_s"]):
            raise TimeoutError("the window did not close")
        time.sleep(POLL_S)
    closed = {n: h.close() for n, h in hists.items()}
    r.compiles.counting = False
    if capture is not None:
        capture.stop()
    t_close = t_open + window[1]
    r.e2e["output_tokens_per_s"] = window[0] / window[1]
    done_in = sum(1 for t, _ in edges if t_open < t <= t_close)
    r.attempted = done_in
    r.failed = 0
    log(f"window {window[1]:.3f}s: {done_in} requests completed, "
        f"compiles in window {r.compiles.compiles + r.compiles.cache_loads}")
    return {"readings": {"hist": closed, "window": (t_open, t_close),
                         "compiles_in_window": r.compiles.compiles
                         + r.compiles.cache_loads},
            "checkable": list(finished)}


def _sample(finished, rids, n, seed) -> list[str]:
    """The longest finished request and ``n - 1`` others drawn from the
    seed."""
    rids = sorted(rids)
    if not rids:
        return []
    longest = max(rids, key=lambda k: len(finished[k][0].prompt)
                  + len(finished[k][1]))
    rest = [k for k in rids if k != longest]
    rng = loadgen.rng_for(seed, 5)
    pick = rng.choice(len(rest), min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[j] for j in sorted(pick)]


def _batch(items, max_len: int):
    """Token rows (prompt + served tokens but the last), the positions
    whose logits chose each served token, the tokens, and a mask."""
    n = len(items)
    t = max(len(toks) for _, toks, _ in items)
    tokens = np.zeros((n, max_len), np.int32)
    at = np.zeros((n, t), np.int32)
    served = np.zeros((n, t), np.int32)
    mask = np.zeros((n, t), bool)
    for i, (q, toks, _) in enumerate(items):
        seq = list(q.prompt) + list(toks[:-1])
        tokens[i, :len(seq)] = seq
        k = len(toks)
        at[i, :k] = np.arange(len(q.prompt) - 1, len(q.prompt) - 1 + k)
        served[i, :k] = toks
        mask[i, :k] = True
    return tokens, at, served, mask


def served_gap(ref, weights, c, items) -> float:
    """Widest gap of a served token's reference logit below the best."""
    import jax
    import jax.numpy as jnp

    if not items:
        return float("inf")
    tokens, at, served, mask = _batch(items, int(c["serving"]["max_len"]))
    fn = jax.jit(lambda w, a, b, s: ref.gaps(w, a, b, s, c))
    g = np.asarray(fn(weights, jnp.asarray(tokens), jnp.asarray(at),
                      jnp.asarray(served)))
    return float(g[mask].max())


def control_checks(r: Run) -> dict:
    return {"served_gap": control_gap(r.reference, r.evidence["weights"],
                                      r.config, r.evidence["items"])}


def control_gap(ref, weights, c, items) -> float:
    """The control's reading on the same positions: the gap of the token
    that the lower precision puts first."""
    import jax
    import jax.numpy as jnp

    tokens, at, served, mask = _batch(items, int(c["serving"]["max_len"]))
    top = jax.jit(lambda w, a, b: ref.control_top(w, a, b, c))(
        weights, jnp.asarray(tokens), jnp.asarray(at))
    fn = jax.jit(lambda w, a, b, s: ref.gaps(w, a, b, s, c))
    g = np.asarray(fn(weights, jnp.asarray(tokens), jnp.asarray(at), top))
    return float(g[mask].max())
