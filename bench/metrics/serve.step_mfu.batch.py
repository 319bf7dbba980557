"""The serve step's share of the chip's bf16 peak over the window: model
FLOPs per processed token (``ops/decoder_step``, at the window's mean
context) times tokens processed per second. Every active slot processes
one token a step, prompt or output: tokens processed = steps (the change
in the ``ksa_serve_step_seconds`` count) times the time-averaged
``ksa_serve_slots_active``. The mean context is the time-averaged
``ksa_serve_pages_used`` times the page size per active slot, less half a
page."""
from harness import load_op
from peaks import peaks
from stats import time_average


def read(r):
    h = (r.get("hist") or {}).get("step")
    if not h or not h["count"]:
        return None
    lo, hi = r["window"]
    g = r["gauges"]
    slots = time_average([(t, a) for t, a, _ in g], lo, hi)
    pages = time_average([(t, p) for t, _, p in g], lo, hi)
    if not slots:
        return None
    context = pages * r["page_size"] / slots - r["page_size"] / 2
    per_token = load_op("decoder_step").flops_per_token(r["model"], context)
    tokens_per_s = h["count"] * slots / (hi - lo)
    return 100.0 * tokens_per_s * per_token \
        / peaks(r["device_kind"])["bf16_flops"]
