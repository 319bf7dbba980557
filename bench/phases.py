"""What the per-layer readers of the program's phase spans share. A phase
(``repro.obs.phase``) is a child span of its task's run in the broker's
span store, which ``campaign_report`` sums per stage under ``phases``, and
a host event of the same name that the profiler stamps on its own clock.
Both return ``None`` where the program records no such phase."""


def screen_phase_ms(r, name: str):
    """Milliseconds of the screen's phase ``name`` per batch, over the
    campaigns that finished in the window: the same tasks and the same
    division as ``knot.screen_ms_per_batch``."""
    stages = [rep["stages"]["screen"] for rep in r.get("campaign_reports")
              or []]
    have = [s["phases"][name] for s in stages if name in s.get("phases", {})]
    tasks = sum(s["tasks"] for s in stages)
    return 1000.0 * sum(have) / tasks if have and tasks else None


def host_span_ms(r, name: str):
    """Mean duration in ms of the host events named ``name`` that lie
    wholly inside the traced stretch."""
    tr = r.get("trace")
    if tr is None:
        return None
    d = [b - a for n, a, b in tr.host
         if n == name and a >= tr.lo and b <= tr.hi]
    return sum(d) / len(d) / 1e6 if d else None
