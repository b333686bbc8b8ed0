"""Device idle time under the program's host spans.

The program opens ``placeit.*`` spans (``src/repro/core/spans.py``) on the
thread that runs the search, the same thread line on which the benchmark
opens its window span.  Idle is the complement of the first device's
busy intervals inside the window; a span's idle time is the part of that
complement the span covers.
"""
from __future__ import annotations

from bench import reduce

PREFIX = "placeit."


def program_spans(tr: reduce.Reduction, name: str | None = None):
    """The program's spans on the window's thread line: those named
    ``name``, or every ``placeit.*`` span where ``name`` is None."""
    lines = {(e.plane, e.line) for e in tr.host if e.name == reduce.WINDOW}
    return [e for e in tr.host if (e.plane, e.line) in lines
            and (e.name == name if name else e.name.startswith(PREFIX))]


def idle_intervals(tr: reduce.Reduction):
    """Stretches of the window in which the first device ran nothing."""
    edges = [tr.t0] + [x for ab in tr.busy_intervals(tr.devices[0])
                       for x in ab] + [tr.t1]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def _overlap(xs, ys) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    tot = 0.0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        tot += max(0.0, hi - lo)
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_ms(tr: reduce.Reduction | None, name: str | None = None):
    """Device-idle ms of the window under the spans ``program_spans``
    picks, or None without a trace, a device or such a span."""
    if tr is None or not tr.devices:
        return None
    spans = program_spans(tr, name)
    if not spans:
        return None
    covered = reduce._union((max(e.start, tr.t0), min(e.end, tr.t1))
                            for e in spans if tr._clip(e) > 0)
    return 1e-6 * _overlap(covered, idle_intervals(tr))


def idle_ms_per(run: dict, name: str, count: str):
    """``idle_ms`` under ``name`` over the run's counter ``count``."""
    ms = idle_ms(run["trace"], name)
    if ms is None or not run[count]:
        return None
    return ms / run[count]
