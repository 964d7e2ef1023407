"""closed loop (harness): the catch-up wall — seconds from the fresh node's
construction to the node standing on the anchor, synced; median over the
rounds that start and end in the window (``bench.round``, recorded by the
generator around a round)."""

import statistics

from benchmarks import spans as SP


def read(run):
    durs = [s.end - s.start for s in SP.named(run["spans"], "bench.round")]
    return statistics.median(durs) if durs else None
