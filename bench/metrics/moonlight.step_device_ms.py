"""Device time of one serve step: the ``jit_serve_step`` module
executions in the traced stretch, total over count."""
from tracing import matching, total_s

MODULE = "jit_serve_step"


def read(r):
    tr = r.get("trace")
    if tr is None or not tr.modules:
        return None
    steps = matching(tr.modules[0], MODULE, tr.lo, tr.hi)
    return 1000.0 * total_s(steps) / len(steps) if steps else None
