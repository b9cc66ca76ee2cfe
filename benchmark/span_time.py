"""Time and counts per query from the program's spans, for the readers in
``metrics/``.

Each traced query of the window carries the program's spans as
``(label, start, end)`` on ``perf_counter``, labelled ``name`` or
``name:node`` (``device.sync:spmd.merge``). A reader returns None where
the program records no span it reads, so a program without them reports
nothing rather than a zero it never measured.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Tuple

Span = Tuple[float, float]


def traced(w) -> list:
    """The window's queries that carry the program's spans."""
    return [s for s in w.sent if s.spans]


def spans(sent, names: Iterable[str]) -> List[Span]:
    """``sent``'s spans whose name (the label up to its first colon) is
    one of ``names``."""
    names = set(names)
    return [(a, b) for label, a, b in sent.spans
            if label.split(":", 1)[0] in names]


def union(intervals: Iterable[Span]) -> List[Span]:
    """The disjoint, sorted union of the intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def overlap(u: List[Span], v: List[Span]) -> float:
    """Time two disjoint, sorted interval lists have in common."""
    i = j = 0
    total = 0.0
    while i < len(u) and j < len(v):
        lo, hi = max(u[i][0], v[j][0]), min(u[i][1], v[j][1])
        if hi > lo:
            total += hi - lo
        if u[i][1] < v[j][1]:
            i += 1
        else:
            j += 1
    return total


def mean_ms(w, names: Iterable[str],
            less: Iterable[str] = ()) -> Optional[float]:
    """Mean per traced query, in ms, of the time covered by the spans
    called ``names``, less the part of it covered by the spans called
    ``less``; None where no query has a span called ``names``."""
    names, less = tuple(names), tuple(less)
    queries = traced(w)
    found, total = False, 0.0
    for s in queries:
        u = union(spans(s, names))
        if not u:
            continue
        found = True
        total += sum(b - a for a, b in u)
        if less:
            total -= overlap(u, union(spans(s, less)))
    return 1000.0 * total / len(queries) if found else None


def mean_count(w, names: Iterable[str]) -> Optional[float]:
    """Mean number of spans called ``names`` per traced query; None where
    there are none."""
    queries = traced(w)
    n = sum(len(spans(s, names)) for s in queries)
    return n / len(queries) if n else None
