"""What the readers of ``zipf1000.frontdoor`` share: the generator's repeats
of the program's chain attributes (``benchmarks/generators/skewed_backlog.py``
``REPEATS``; ``spans.compact`` keeps none of those spans' attributes)."""

import statistics

from benchmarks import spans as SP


def median_attr(run, span: str, key: str):
    """Median over the window of ``key`` on the harness's ``span``; None
    where the program recorded no such attribute (the parent commit)."""
    vals = [s.attrs[key] for s in SP.named(run["spans"], span)]
    return float(statistics.median(vals)) if vals else None
