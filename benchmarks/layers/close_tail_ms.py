"""apply / commit: the highest percentile of close time with ten samples
beyond it in the traced window (which percentile is printed)."""

from benchmarks import stats


def read(run):
    t = stats.tail_percentile([r.end - r.start for r in run["readings"]])
    if t is None:
        return None
    print("close_tail_ms: the %.1fth percentile of %d closes" % (t[0], len(run["readings"])), flush=True)
    return t[1] * 1e3
