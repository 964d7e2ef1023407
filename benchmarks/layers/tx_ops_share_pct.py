"""apply / commit (tx/frame.py): the share of the window's sampled
``tx.apply`` time spent in the operation loop (``tx.ops``); the rest is
``tx.valid`` (source load, sequence, signatures) and ``tx.apply``'s own
time (the deltas' commits, the result pair, the history row)."""

from benchmarks import spans as SP


def read(run):
    total = SP.seconds(run["spans"], "tx.apply")
    if total <= 0:
        return None
    return 100.0 * SP.seconds(run["spans"], "tx.ops") / total
