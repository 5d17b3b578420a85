"""What the per-layer readers of the program's host spans share.

The program opens ``repro.obs.trace.span(name)`` around its host work; a
traced run finds each on the profiler's host plane as
``"<thread>: <name>"`` (``trace_reduce.load`` keeps every host event,
clipped to the window). A reader matches the name after that prefix,
so the harness's own labels, which hold ``": "`` themselves, never
match. Times are in seconds, as in ``trace_reduce``.
"""
from __future__ import annotations

import re
from typing import List, Optional, Tuple


def pattern(*names: str) -> str:
    """The pattern of a host event named one of ``names``."""
    return r"^[^:]*: (%s)$" % "|".join(re.escape(n) for n in names)


def matching(trace, pat: str) -> List[Tuple[float, float]]:
    """Every host event matching ``pat``, as clipped to the window."""
    rx = re.compile(pat)
    return [(s, e) for s, e, n in trace.host if rx.search(n)]


def mean_ms(trace, pat: str) -> Optional[float]:
    """Mean length of the matching spans that lie wholly inside the
    window (one clipped at an edge would read short); None if none
    does."""
    w0, w1 = trace.window
    inside = [e - s for s, e in matching(trace, pat) if w0 < s and e < w1]
    if not inside:
        return None
    return 1e-6 * sum(inside) / len(inside)


def union(intervals) -> List[Tuple[float, float]]:
    """Overlapping and nested intervals merged, in order."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals, cover) -> float:
    """How much of ``intervals`` (disjoint) the disjoint, sorted
    ``cover`` overlaps."""
    total, j = 0.0, 0
    for s, e in intervals:
        while j < len(cover) and cover[j][1] <= s:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            total += min(e, cover[k][1]) - max(s, cover[k][0])
            k += 1
    return total
