"""The writhe kernel's share of its roofline over the traced stretch.

Each kernel event is matched to the task run span that was open when it
ran, which gives its batch (kept structures for a screen, survivors for a
localize); ``ops/writhe`` turns that into FLOPs and bytes. The VPU has no
published peak, so the compute bound uses the bf16 MXU peak and reads low;
the bytes bound (writing the map) is the one that applies."""
from harness import load_op
from peaks import roofline_share
from tracing import host_activity, matching, total_s

KERNEL = "writhe_map"


def read(r):
    tr = r.get("trace")
    if tr is None or not tr.ops:
        return None
    events = matching(tr.ops[0], KERNEL, tr.lo, tr.hi)
    if not events:
        return None
    cost = load_op("writhe").cost
    spans = [(f"{s}|{n}", tr.to_ns(a), tr.to_ns(b))
             for s, a, b, n in r["task_spans"] if n]
    flops = nbytes = 0.0
    for _, a, b in events:
        label = host_activity(spans, (a + b) // 2)
        if label == "none":
            return None
        f, by = cost(int(label.split("|")[1]), r["n_points"])
        flops += f
        nbytes += by
    share = roofline_share(flops, nbytes, total_s(events), r["device_kind"])
    return None if share is None else share[0]
