"""Mean host time of one engine step over the window: the change in
``ksa_serve_step_seconds`` sum over the change in its count."""


def read(r):
    h = (r.get("hist") or {}).get("step")
    if not h or not h["count"]:
        return None
    return 1000.0 * h["sum"] / h["count"]
