"""Idle milliseconds per traced fit put down to what the host was doing:
the time in which no operation ran on the busiest chip and the host was
inside a span whose name matches ``spans``, less the part inside a span
matching ``exclude``, clipped to the benchmark's own fit spans (names that
start with ``inside``). By interval overlap: a gap that straddles two spans
is split between them, where a midpoint would give it all to one.

The program writes its spans into the profiler's capture as
``cyclone.<kind>.<name>``. A capture that holds none of them gives ``None``:
an emitter that broke drops the metric, it does not report 0."""

import re

from perfbench.trace import busy, gaps, seconds, union

PROGRAM_PREFIX = "cyclone."


def overlap(a, b):
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def spans_matching(host, pattern):
    rx = re.compile(pattern)
    return union((s, s + d) for name, s, d in host if rx.search(name))


def read(run, spans, exclude=None, inside="perfbench.fit"):
    tr, fits = run["trace"], run["traced_fits"]
    if not fits or not any(name.startswith(PROGRAM_PREFIX)
                           for name, _, _ in tr.host):
        return None
    # a platform with no device plane (a rehearsal) was idle throughout
    device = tr.chips[tr.fullest_chip()] if tr.chips else []
    idle = gaps(busy(device), tr.window)
    idle = overlap(idle, union((s, s + d) for name, s, d in tr.host
                               if name.startswith(inside)))
    idle = overlap(idle, spans_matching(tr.host, spans))
    if exclude:
        idle = overlap(idle, gaps(spans_matching(tr.host, exclude),
                                  tr.window))
    return 1e3 * seconds(idle) / len(fits)
