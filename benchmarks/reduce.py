"""From a profiler trace (``.xplane.pb``) and host spans to device numbers.

``jax.profiler.ProfileData`` reads the file with nothing but JAX.  On a TPU
the trace has one plane per chip, ``/device:TPU:<n>``, whose ``XLA Ops``
line holds one event per operation that ran on the chip (start and duration
in nanoseconds since the profile began).  Host events sit on ``/host:CPU``; the
harness writes ``bench.sync.<time.monotonic_ns()>`` annotations there, which
give the offset between the trace's clock and ``time.monotonic`` so that
spans recorded by the program's tracer can be laid over device events.

* busy: the union of the device's operation intervals inside the window;
* idle share: 1 - busy / window (the driver works it out from the two);
* kernel time: the sum of the durations of the operations whose name
  matches;
* idle gaps: each maximal interval with no operation running, charged to
  the innermost host span open during it.
"""

from __future__ import annotations

import heapq
import re
import statistics
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

SYNC_PREFIX = "bench.sync."
NO_SPAN = "_no_host_span_open_"


class Op(NamedTuple):
    name: str
    start_ns: float
    end_ns: float


class Trace(NamedTuple):
    chips: Dict[str, List[Op]]  # device plane -> operations, by start
    offset_ns: Optional[float]  # trace clock minus time.monotonic_ns
    sync_markers: int


_HLO = re.compile(r"^%?([^ =]+) = \(?([a-z0-9]+\[[0-9,]*\])")


def short_name(name: str) -> str:
    """``%verify_kernel_pallas.1 = s32[1,4096]{...} custom-call(...)`` ->
    ``verify_kernel_pallas.1 s32[1,4096]``; other names unchanged."""
    m = _HLO.match(name)
    if m:
        return f"{m.group(1)} {m.group(2)}"
    return name.lstrip("%")


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    chips: Dict[str, List[Op]] = {}
    offsets = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    chips[plane.name] = sorted(
                        (
                            Op(short_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                        ),
                        key=lambda o: o.start_ns,
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SYNC_PREFIX):
                        offsets.append(e.start_ns - int(e.name[len(SYNC_PREFIX) :]))
    return Trace(chips, statistics.median(offsets) if offsets else None, len(offsets))


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def busy_intervals(ops: Sequence[Op], w0: float, w1: float) -> List[Tuple[float, float]]:
    return _union((max(o.start_ns, w0), min(o.end_ns, w1)) for o in ops if o.end_ns > w0 and o.start_ns < w1)


def busy_seconds(trace: Trace, w0: float, w1: float) -> float:
    """Seconds in which an operation ran, averaged over the chips traced."""
    if not trace.chips:
        return 0.0
    per_chip = [
        sum(hi - lo for lo, hi in busy_intervals(ops, w0, w1)) / 1e9
        for ops in trace.chips.values()
    ]
    return sum(per_chip) / len(per_chip)


def op_seconds(trace: Trace, w0: float, w1: float) -> Dict[str, float]:
    """Device seconds by operation name, inside the window, summed over chips."""
    out: Dict[str, float] = {}
    for ops in trace.chips.values():
        for o in ops:
            lo, hi = max(o.start_ns, w0), min(o.end_ns, w1)
            if hi > lo:
                out[o.name] = out.get(o.name, 0.0) + (hi - lo) / 1e9
    return out


def op_count(trace: Trace, w0: float, w1: float, pattern: str) -> int:
    rx = re.compile(pattern)
    return sum(
        1
        for ops in trace.chips.values()
        for o in ops
        if rx.search(o.name) and o.start_ns >= w0 and o.end_ns <= w1
    )


def idle_gaps(
    trace: Trace, spans: Sequence[Tuple[str, float, float]], w0: float, w1: float
) -> Dict[str, float]:
    """Idle seconds of the first chip by the innermost host span open in
    them.  ``spans``: (name, start_ns, end_ns) on the trace's clock."""
    if not trace.chips:
        return {}
    ops = next(iter(trace.chips.values()))
    busy = busy_intervals(ops, w0, w1)
    gaps, t = [], w0
    for lo, hi in busy:
        if lo > t:
            gaps.append((t, lo))
        t = max(t, hi)
    if w1 > t:
        gaps.append((t, w1))
    # sweep: at each boundary the innermost open span is the one that
    # started last
    points = sorted({p for g in gaps for p in g} | {p for s in spans for p in (s[1], s[2]) if w0 <= p <= w1})
    by_start = sorted(spans, key=lambda s: s[1])
    heap: list = []  # (-start, end, name)
    out: Dict[str, float] = {}
    gi = si = 0
    for a, b in zip(points, points[1:]):
        while gi < len(gaps) and gaps[gi][1] <= a:
            gi += 1
        if gi >= len(gaps):
            break
        if not (gaps[gi][0] <= a and b <= gaps[gi][1]):
            continue
        while si < len(by_start) and by_start[si][1] <= a:
            s = by_start[si]
            heapq.heappush(heap, (-s[1], s[2], s[0]))
            si += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        name = heap[0][2] if heap else NO_SPAN
        out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
