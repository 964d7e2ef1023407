"""Host spans as the layer readers see them: ``S(name, start, end, tid,
attrs)`` with seconds on ``time.monotonic``.  The program's spans
(``stellar_tpu/trace``) and the harness's own (``bench.*``, recorded around
the calls into each layer) share the clock and the type."""

from __future__ import annotations

import statistics
from typing import Callable, Iterable, Iterator, List, NamedTuple, Optional, Sequence


class S(NamedTuple):
    name: str
    start: float
    end: float
    tid: int
    attrs: Optional[dict]


KEEP_ATTRS = ("ed25519.host_hash", "ed25519.device_dispatch")


def compact(spans: Iterable) -> List[S]:
    """The program's spans as plain tuples, attributes kept only where a
    reader needs them.  The harness holds every span of the window; as
    tuples of numbers and strings they cost the collector nothing, whereas
    the program's ``Span`` objects with their dicts would be ~6,000 more
    tracked objects a ledger in the front door."""
    return [
        S(s.name, s.start, s.end, s.tid, dict(s.attrs) if s.attrs and s.name in KEEP_ATTRS else None)
        for s in spans
        if s.end is not None
    ]


def named(spans: Sequence[S], *names: str) -> List[S]:
    return [s for s in spans if s.name in names]


def seconds(spans: Sequence[S], *names: str) -> float:
    return sum(s.end - s.start for s in spans if s.name in names)


def seconds_excluding(spans: Sequence[S], name: str, *nested: str) -> float:
    """Seconds of ``name`` spans less the ``nested`` spans of the same
    thread that lie inside them."""
    total = 0.0
    inner = [s for s in spans if s.name in nested]
    for s in spans:
        if s.name != name:
            continue
        total += s.end - s.start
        for c in inner:
            if c.tid == s.tid and c.start >= s.start and c.end <= s.end:
                total -= c.end - c.start
    return total


def by_reading(spans: Sequence[S], readings: Sequence) -> Iterator[List[S]]:
    """For each reading, in time order, the spans that start inside it."""
    ordered = sorted(spans, key=lambda s: s.start)
    i = 0
    for r in sorted(readings, key=lambda r: r.start):
        while i < len(ordered) and ordered[i].start < r.start:
            i += 1
        j = i
        while j < len(ordered) and ordered[j].start <= r.end:
            j += 1
        yield ordered[i:j]
        i = j


def per_reading_median(
    spans: Sequence[S], readings: Sequence, fn: Callable[[List[S]], Optional[float]]
) -> Optional[float]:
    """Median over the readings of ``fn(spans that start inside the
    reading)``; readings for which ``fn`` returns None are left out."""
    vals = [v for v in map(fn, by_reading(spans, readings)) if v is not None]
    return statistics.median(vals) if vals else None
