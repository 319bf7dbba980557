"""The paged flash-decode kernel's share of its roofline over the traced
stretch. Every call is one layer of one step; its bytes and FLOPs
(``ops/flash_decode``) follow from the occupied pages, the
time-averaged ``ksa_serve_pages_used`` over the stretch."""
from harness import load_op
from peaks import roofline_share
from stats import time_average
from tracing import matching, total_s

KERNEL = "flash_decode_paged"


def read(r):
    tr = r.get("trace")
    if tr is None or not tr.ops:
        return None
    calls = matching(tr.ops[0], KERNEL, tr.lo, tr.hi)
    if not calls:
        return None
    lo, hi = tr.wall_lo, tr.wall_lo + tr.window_s
    pages = time_average([(t, p) for t, _, p in r["gauges"]], lo, hi)
    if not pages:
        return None
    m = r["model"]
    flops, nbytes = load_op("flash_decode").cost(
        pages, r["n_slots"], m["n_heads"], m["n_kv_heads"], m["head_dim"],
        r["page_size"])
    share = roofline_share(len(calls) * flops, len(calls) * nbytes,
                           total_s(calls), r["device_kind"])
    return None if share is None else share[0]
