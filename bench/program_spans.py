"""The program's own host spans in a reduced trace.

The program marks its layer boundaries with ``jax.profiler.TraceAnnotation``
(``bring_up``, ``checkpoint/*``, ``generate``, ``schedule/wait_arrival``,
``fleet/to_host``, ...).  They run on the thread that drives the window, so
``trace_reduce`` keeps them in ``Reduced.host`` beside the benchmark's own
``bench/`` spans, on the device's clock.  A trace of a program without
them gives empty lists here, and each reader None.
"""
from __future__ import annotations

from typing import Optional


def spans(trace, names, within: Optional[tuple[float, float]] = None) -> list:
    """``(start, end)`` of every host span named in ``names`` (a name or a
    tuple of names) that lies wholly inside ``within`` (default: the
    window)."""
    names = (names,) if isinstance(names, str) else tuple(names)
    t0, t1 = within or trace.window
    return [(s, e) for n, s, e in trace.host if n in names and s >= t0 and e <= t1]


def inside(inner: list, outer: list) -> list:
    """The spans of ``inner`` that lie wholly inside one of ``outer``."""
    return [(s, e) for s, e in inner if any(a <= s and e <= b for a, b in outer)]


def per_bring_up(run, names) -> Optional[float]:
    """Seconds per ``bring_up`` span in the window spent in the child spans
    named ``names``; None without a bring-up or such a child."""
    if run.trace is None:
        return None
    ups = spans(run.trace, "bring_up")
    children = inside(spans(run.trace, names), ups)
    if not children:
        return None
    return sum(e - s for s, e in children) / len(ups)


def idle_in(trace, intervals) -> float:
    """Seconds of chip idle inside the given intervals (which do not
    overlap)."""
    return sum(b - a for iv in intervals for a, b in trace.gaps(within=iv))
