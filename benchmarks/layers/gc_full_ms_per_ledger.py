"""collector (util/collector.py): the window's ``gc.full`` seconds — every
generation-2 pass of the process, whoever asked — over the window's
readings.  A mean, not a median over the cycles: a pass falls in one cycle
of four, so the median cycle holds none and would read 0.  A window in
which no pass ran reads 0 (a rehearsal's few ledgers): the span has been
the program's since PR 29, so no span is no seconds."""

from benchmarks import spans as SP


def read(run):
    if not run["readings"]:
        return None
    return SP.seconds(run["spans"], "gc.full") * 1e3 / len(run["readings"])
