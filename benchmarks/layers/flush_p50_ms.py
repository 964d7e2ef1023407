"""closed loop (harness): median seconds of one flush (one call of
``verify_batch`` over a full set), milliseconds, over the flushes that start
and end in the window.  The level of the process: where runs differ in it,
every flush was slower, not a few."""

import statistics


def read(run):
    durs = [r.end - r.start for r in run["readings"]]
    if not durs:
        return None
    return statistics.median(durs) * 1e3
