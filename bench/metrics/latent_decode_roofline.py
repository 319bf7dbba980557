"""The paged latent decode kernel's share of its roofline over the traced
stretch. Every ``flash_decode_latent`` call is one layer of one step; its
bytes and FLOPs (``ops/latent_decode``) follow from the occupied pages,
the time-averaged ``ksa_serve_pages_used`` over the stretch."""
from harness import load_op
from peaks import roofline_share
from stats import time_average
from tracing import matching, total_s

KERNEL = "flash_decode_latent"


def read(r):
    tr = r.get("trace")
    m = r.get("model") or {}
    if tr is None or not tr.ops or "mla" not in m:
        return None
    calls = matching(tr.ops[0], KERNEL, tr.lo, tr.hi)
    if not calls:
        return None
    lo, hi = tr.wall_lo, tr.wall_lo + tr.window_s
    pages = time_average([(t, p) for t, _, p in r["gauges"]], lo, hi)
    if not pages:
        return None
    mla = m["mla"]
    flops, nbytes = load_op("latent_decode").cost(
        pages, r["n_slots"], m["n_heads"], mla["kv_lora_rank"],
        mla["rope_head_dim"], r["page_size"])
    share = roofline_share(len(calls) * flops, len(calls) * nbytes,
                           total_s(calls), r["device_kind"])
    return None if share is None else share[0]
