"""What the readers of ``catchup64.replay`` share.  The program's spans reach
a reader without their attributes (``benchmarks.spans.compact``), so every
number here is a count or a duration of spans, or a difference of counters."""

from __future__ import annotations

import statistics

from benchmarks import spans as SP


def median_ms(run: dict, *names: str):
    """Median duration, in ms, of the window's spans of these names; None
    where the program records none."""
    durs = [s.end - s.start for s in SP.named(run["spans"], *names)]
    return statistics.median(durs) * 1e3 if durs else None


def rounds_in_window(run: dict):
    """Rounds the window held, counted in replayed ledgers: the generator's
    own count, so that a program without catch-up counters has one too."""
    try:
        after, before = run["counters"]["after"]["replay"], run["counters"]["before"]["replay"]
    except KeyError:
        return None
    return (after["ledgers"] - before["ledgers"]) / after["ledgers_per_round"]
