"""Profiler capture and the reduction from a trace to numbers.

``Capture`` records a JAX profiler trace with the Python tracer off and two
marker annotations (``bench.open`` / ``bench.close``) whose wall-clock
times are known, so the program's own spans (taken on ``time.time()``) can
be put on the trace's clock. ``load`` turns the ``.xplane.pb`` into plain
interval lists; everything after that is pure arithmetic on
``(name, start_ns, end_ns)`` tuples, checked on synthetic traces in
``bench/tests/test_trace.py``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from pathlib import Path

OPEN, CLOSE = "bench.open", "bench.close"
DEVICE_OPS_LINE = "XLA Ops"
DEVICE_MODULES_LINE = "XLA Modules"


@dataclass
class Trace:
    """Intervals in ns on the trace's clock. ``ops`` and ``modules`` hold
    one list per device; ``host`` holds every host-thread event plus the
    program spans added by :meth:`add_wall_spans`."""
    lo: int
    hi: int
    wall_lo: float
    ops: list[list[tuple]] = field(default_factory=list)
    modules: list[list[tuple]] = field(default_factory=list)
    host: list[tuple] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def to_ns(self, wall: float) -> int:
        return self.lo + int(round((wall - self.wall_lo) * 1e9))

    def add_wall_spans(self, spans) -> None:
        """Add host spans given as ``(name, wall_start, wall_end)``."""
        for name, a, b in spans:
            self.host.append((name, self.to_ns(a), self.to_ns(b)))

    def busy_s(self) -> float:
        """Device-busy seconds in the window, averaged over devices."""
        if not self.ops:
            return 0.0
        return sum(union_ns(d, self.lo, self.hi) for d in self.ops) \
            / len(self.ops) / 1e9


class Capture:
    """Start and stop one profiler trace into ``log_dir``."""

    def __init__(self, log_dir: Path):
        self.log_dir = Path(log_dir)
        self.wall_open = self.wall_close = None

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
        with jax.profiler.TraceAnnotation(OPEN):
            self.wall_open = time.time()

    def stop(self) -> None:
        import jax
        with jax.profiler.TraceAnnotation(CLOSE):
            self.wall_close = time.time()
        jax.profiler.stop_trace()

    def load(self) -> Trace:
        return load(self.log_dir, self.wall_open)


def load(log_dir: Path, wall_open: float) -> Trace:
    import jax
    files = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(str(files[-1]))
    ops, modules, host = [], [], []
    marks = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            d_ops, d_mods = [], []
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    d_ops.extend((e.name, int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
                                 for e in line.events)
                elif line.name == DEVICE_MODULES_LINE:
                    d_mods.extend((e.name, int(e.start_ns),
                                   int(e.start_ns + e.duration_ns))
                                  for e in line.events)
            if d_ops or d_mods:
                ops.append(d_ops)
                modules.append(d_mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    s, t = int(e.start_ns), int(e.start_ns + e.duration_ns)
                    if e.name in (OPEN, CLOSE):
                        marks[e.name] = s
                    else:
                        host.append((e.name, s, t))
    if OPEN not in marks or CLOSE not in marks:
        raise ValueError("the trace lacks the bench.open/close markers")
    return Trace(lo=marks[OPEN], hi=marks[CLOSE], wall_lo=wall_open,
                 ops=ops, modules=modules, host=host)


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------


def merged(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Union of ``(name, start, end)`` intervals clipped to [lo, hi]."""
    spans = sorted((max(a, lo), min(b, hi)) for _, a, b in intervals
                   if b > lo and a < hi)
    out: list[list[int]] = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def union_ns(intervals, lo: int, hi: int) -> int:
    return sum(b - a for a, b in merged(intervals, lo, hi))


def gaps(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Idle intervals of [lo, hi] not covered by any interval."""
    out, cur = [], lo
    for a, b in merged(intervals, lo, hi):
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        out.append((cur, hi))
    return out


def matching(intervals, pattern: str, lo: int, hi: int) -> list[tuple]:
    """Events in [lo, hi] whose op name contains ``pattern``."""
    return [(n, a, b) for n, a, b in intervals
            if pattern in op_name(n) and a >= lo and b <= hi]


def total_s(intervals) -> float:
    return sum(b - a for _, a, b in intervals) / 1e9


def host_activity(host, t: int) -> str:
    """What the host was doing at ``t``: the innermost (shortest) host span
    open then, or ``none``."""
    best = None
    for name, a, b in host:
        if a <= t < b and (best is None or b - a < best[1] - best[0]):
            best = (a, b, name)
    return best[2] if best else "none"


def idle_by_host(trace: Trace, top: int = 10) -> list[list]:
    """Idle seconds of the device (the first one) summed by the host
    activity open at the middle of each gap; the ``top`` largest."""
    if not trace.ops:
        return []
    acc: dict[str, int] = {}
    for a, b in gaps(trace.ops[0], trace.lo, trace.hi):
        key = host_activity(trace.host, (a + b) // 2)
        acc[key] = acc.get(key, 0) + (b - a)
    return [[k, v / 1e9] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:top]]


def op_name(event_name: str) -> str:
    """An XLA op event is named by its HLO text; the instruction's own name
    (``%fusion.12``, ``%writhe_map.1``) is what stays the same across the
    shapes it runs at."""
    return event_name.split(" = ", 1)[0]


def self_times(intervals) -> list[tuple]:
    """Each event's time less the time of the events nested in it (a
    ``while`` op holds its body's ops on the same line)."""
    out, stack = [], []
    for n, a, b in sorted(intervals, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            out.append(tuple(stack.pop()[:2]))
        if stack:
            stack[-1][1] -= min(b, stack[-1][2]) - a
        stack.append([n, b - a, b])
    out.extend(tuple(e[:2]) for e in stack)
    return out


def top_ops(trace: Trace, top: int = 10) -> list[list]:
    """Device self time by operation (the first device), the ``top``."""
    if not trace.ops:
        return []
    acc: dict[str, int] = {}
    clipped = [(n, max(a, trace.lo), min(b, trace.hi))
               for n, a, b in trace.ops[0] if b > trace.lo and a < trace.hi]
    for n, t in self_times(clipped):
        n = op_name(n)
        acc[n] = acc.get(n, 0) + t
    return [[k, v / 1e9] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:top]]


def breakdown(trace: Trace) -> dict:
    return {"device_ops": top_ops(trace), "idle_gaps": idle_by_host(trace)}
