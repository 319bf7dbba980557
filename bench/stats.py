"""Spread and window arithmetic shared by the drivers and the
metric readers. Pure Python: no JAX, no program imports."""
from __future__ import annotations

import statistics


def spread(values) -> float:
    """Interquartile distance as a share of the median, with the quartiles
    of ``statistics.quantiles(values, n=4)``."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(med)


def rate_between_edges(edges, opened: float, seconds: float):
    """A rate over whole units of work. ``edges`` is a time-ordered list of
    ``(t, amount)`` completions. The window opens at ``opened`` (itself a
    completion time) and closes at the first completion at or after
    ``opened + seconds``. Returns ``(amount completed in (opened, closed],
    closed - opened)``, or ``None`` while the window has not closed."""
    done = 0.0
    for t, amount in edges:
        if t <= opened:
            continue
        done += amount
        if t >= opened + seconds:
            return done, t - opened
    return None


def time_average(samples, start: float, end: float) -> float | None:
    """Time-weighted mean over [start, end] of a step function sampled as
    ``(t, value)`` pairs in time order (each value holds until the next
    sample). ``None`` without a sample inside the interval."""
    total = 0.0
    prev_t, prev_v = None, None
    for t, v in samples:
        if t <= start:
            prev_t, prev_v = start, v
            continue
        if t > end:
            break
        if prev_v is not None:
            total += prev_v * (t - prev_t)
        prev_t, prev_v = t, v
    if prev_v is None or end <= start:
        return None
    total += prev_v * (end - prev_t)
    return total / (end - start)
