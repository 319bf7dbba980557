"""The serve step's share of the chip's bf16 peak over the window: model
FLOPs per processed token (``ops/moe_mla_step``: the top-k and shared
experts, the absorbed latent decode, the dense layer and the unembedding,
at the window's mean context) times tokens processed per second. Every
active slot processes one token a step, prompt or output: tokens
processed = steps (the change in the ``ksa_serve_step_seconds`` count)
times the time-averaged ``ksa_serve_slots_active``. The mean context is
the time-averaged ``ksa_serve_pages_used`` times the page size per active
slot, less half a page. Nothing to read in a configuration without latent
attention and experts."""
from harness import load_op
from peaks import peaks
from stats import time_average


def read(r):
    h = (r.get("hist") or {}).get("step")
    m = r.get("model") or {}
    if not h or not h["count"] or "mla" not in m or "moe" not in m:
        return None
    lo, hi = r["window"]
    g = r["gauges"]
    slots = time_average([(t, a) for t, a, _ in g], lo, hi)
    pages = time_average([(t, p) for t, _, p in g], lo, hi)
    if not slots:
        return None
    context = pages * r["page_size"] / slots - r["page_size"] / 2
    per_token = load_op("moe_mla_step").flops_per_token(m, context)
    tokens_per_s = h["count"] * slots / (hi - lo)
    return 100.0 * tokens_per_s * per_token \
        / peaks(r["device_kind"])["bf16_flops"]
