"""The serve step's share of its HBM roofline over the traced stretch:
the bytes one step must read (``ops/moe_mla_step``: every weight but the
embedding table, plus the occupied latent pages at the stretch's
time-averaged ``ksa_serve_pages_used``) over the mean ``jit_serve_step``
module time, as a share of the chip's HBM bandwidth. Nothing to read in a
configuration without latent attention and experts."""
from harness import load_op
from peaks import peaks
from stats import time_average
from tracing import matching, total_s

MODULE = "jit_serve_step"


def read(r):
    tr = r.get("trace")
    m = r.get("model") or {}
    if tr is None or not tr.modules or "mla" not in m or "moe" not in m:
        return None
    steps = matching(tr.modules[0], MODULE, tr.lo, tr.hi)
    if not steps:
        return None
    lo, hi = tr.wall_lo, tr.wall_lo + tr.window_s
    pages = time_average([(t, p) for t, _, p in r["gauges"]], lo, hi)
    if pages is None:
        return None
    nbytes = load_op("moe_mla_step").step_bytes(m, pages, r["page_size"])
    seconds = total_s(steps) / len(steps)
    return 100.0 * nbytes / peaks(r["device_kind"])["hbm_bytes_per_s"] \
        / seconds
