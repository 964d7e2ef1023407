"""closed loop (harness): the share of the window's seconds spent in
flushes that took more than twice the median flush, beyond that median.
What the rate (all work over all the window) loses to stalls, as opposed to
a level at which every flush is slower (``flush_p50_ms``)."""

import statistics


def read(run):
    durs = [r.end - r.start for r in run["readings"]]
    if not durs:
        return None
    p50 = statistics.median(durs)
    lost = sum(d - p50 for d in durs if d > 2.0 * p50)
    t_open, t_end = run["window"]
    return 100.0 * lost / (t_end - t_open)
