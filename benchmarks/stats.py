"""Arithmetic from a window's readings to a run's metrics.

A reading is ``(start, end, items)``: one unit of the cell's work (a flush,
a close, a ledger cycle) with the seconds, on ``time.monotonic``, at which
it started and ended, and the items it completed.  A rate is all the items
completed in the window over the window's seconds, so that a stall inside
the window costs what a user of the validator loses by it; a close time is
the median over all the closes that start and end in the window.  The median
of the window's slice rates, which a stall hardly moves, stands beside the
rate as a per-layer diagnostic: when the two part, the window held a stall.
Nothing here touches JAX or the program.
"""

from __future__ import annotations

import math
import statistics
from typing import List, NamedTuple, Optional, Sequence, Tuple


class Reading(NamedTuple):
    start: float
    end: float
    items: int


class Median(NamedTuple):
    """A run's metric and the number of readings behind it."""

    value: float
    samples: int


def in_window(readings: Sequence[Reading], t_open: float, t_close: float) -> List[Reading]:
    """Readings that both start and end inside the window."""
    return [r for r in readings if r.start >= t_open and r.end <= t_close]


def duration_median(
    readings: Sequence[Reading], t_open: float, t_close: float
) -> Optional[Median]:
    """Median seconds of the readings that start and end in the window."""
    durs = [r.end - r.start for r in in_window(readings, t_open, t_close)]
    if not durs:
        return None
    return Median(statistics.median(durs), len(durs))


def work_over_wall(readings: Sequence[Reading], t_open: float, t_close: float) -> Optional[Median]:
    """All items completed in the window over the window's seconds.  A
    reading that straddles an edge is credited in proportion to the part
    of it that lies inside, as ``slice_rates`` does."""
    total, n = 0.0, 0
    for r in readings:
        if r.end <= t_open or r.start >= t_close or r.items == 0:
            continue
        span = r.end - r.start
        inside = min(r.end, t_close) - max(r.start, t_open)
        total += r.items * inside / span if span > 0.0 else r.items
        n += 1
    if not n:
        return None
    return Median(total / (t_close - t_open), n)


def slice_rates(
    readings: Sequence[Reading], t_open: float, t_close: float, slice_s: float = 1.0
) -> List[float]:
    """Completion rate, items per second, of each whole slice of the window.

    A reading's items are credited over the interval in which they were
    worked on, ``[start, end)``, in proportion to its overlap with each
    slice.  Crediting all of a reading to the slice that holds its end
    would quantise a slice's rate to whole readings: a 5,000-item flush is
    1.7 % of a second's work and a 1,000-tx ledger cycle longer than the
    slice, so the median would hop between multiples of one reading.  The
    sum over slices is unchanged.  A trailing part-slice is dropped."""
    n = int(math.floor((t_close - t_open) / slice_s + 1e-9))
    acc = [0.0] * n
    for r in readings:
        if r.end <= t_open or r.start >= t_open + n * slice_s or r.items == 0:
            continue
        span = r.end - r.start
        if span <= 0.0:
            k = int((r.end - t_open) / slice_s)
            if 0 <= k < n:
                acc[k] += r.items
            continue
        first = max(0, int((r.start - t_open) / slice_s))
        last = min(n - 1, int((r.end - t_open) / slice_s))
        for k in range(first, last + 1):
            lo = max(r.start, t_open + k * slice_s)
            hi = min(r.end, t_open + (k + 1) * slice_s)
            if hi > lo:
                acc[k] += r.items * (hi - lo) / span
    return [a / slice_s for a in acc]


def slice_rate_median(
    readings: Sequence[Reading], t_open: float, t_close: float, slice_s: float = 1.0
) -> Optional[Median]:
    rates = slice_rates(readings, t_open, t_close, slice_s)
    if not rates:
        return None
    return Median(statistics.median(rates), len(rates))


def reduce(how: dict, readings: Sequence[Reading], t_open: float, t_close: float) -> Optional[Median]:
    """A traffic file's end-to-end entry applied to a window: ``reduce`` is
    ``work_over_wall`` or ``duration_median``."""
    if how["reduce"] == "work_over_wall":
        return work_over_wall(readings, t_open, t_close)
    if how["reduce"] == "duration_median":
        return duration_median(readings, t_open, t_close)
    raise ValueError(f"unknown reduction {how['reduce']!r}")


def tail_percentile(values: Sequence[float], beyond: int = 10) -> Optional[Tuple[float, float]]:
    """The highest percentile with ``beyond`` samples beyond it:
    ``(percentile, value)``, or None when the sample is too small to have
    one above the median."""
    n = len(values)
    if n < 2 * beyond + 1:
        return None
    ordered = sorted(values)
    idx = n - beyond - 1
    return 100.0 * (idx + 1) / n, ordered[idx]


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, as the driver reads it (``statistics.quantiles(n=4)``)."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def windows_of(
    readings: Sequence[Reading], how: dict, length_s: float, step_s: float = 1.0
) -> List[float]:
    """What a run of ``length_s`` would have read at every contiguous
    window of a long recorded series (step 1.4 of the issue: the choice of
    ``run_seconds``)."""
    t0 = min(r.start for r in readings)
    t1 = max(r.end for r in readings)
    out = []
    t = t0
    while t + length_s <= t1:
        m = reduce(how, readings, t, t + length_s)
        if m is not None:
            out.append(m.value)
        t += step_s
    return out
