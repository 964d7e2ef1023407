"""Self time from parents: a span's duration less the union of its direct
children's intervals (children of other threads overlap each other, and a
worker's span may outlive the caller's: both are clipped to the parent)."""

from __future__ import annotations

import statistics
from typing import Dict, Iterable, List


def self_times(spans: Iterable) -> Dict[int, float]:
    """``sid`` -> seconds of each completed span that none of its direct
    children covers.  Children are found by ``parent``, on any thread."""
    spans = [s for s in spans if s.end is not None]
    kids: Dict[int, List] = {}
    for s in spans:
        if s.parent is not None:
            kids.setdefault(s.parent, []).append(s)
    out = {}
    for s in spans:
        covered, cursor = 0.0, s.start
        for c in sorted(kids.get(s.sid, ()), key=lambda c: c.start):
            lo, hi = max(c.start, cursor), min(c.end, s.end)
            if hi > lo:
                covered += hi - lo
                cursor = hi
        out[s.sid] = (s.end - s.start) - covered
    return out


def self_p50_ms(spans: Iterable) -> Dict[str, float]:
    """Per span name, the median self time in milliseconds over ``spans``
    (the ring as ``/trace`` dumps it: a child that the ring has already
    overwritten no longer counts against its parent)."""
    spans = list(spans)
    selfs = self_times(spans)
    by_name: Dict[str, List[float]] = {}
    for s in spans:
        if s.sid in selfs:
            by_name.setdefault(s.name, []).append(selfs[s.sid] * 1000.0)
    return {name: statistics.median(v) for name, v in sorted(by_name.items())}
