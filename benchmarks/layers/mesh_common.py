"""What the readers of the four-chip cell share: each chip's busy seconds
in the timed window, from its own plane of the device trace."""

from __future__ import annotations

from typing import List

from benchmarks import reduce as R


def chip_busy_seconds(run: dict) -> List[float]:
    """Seconds in which an operation ran, a chip, in the planes' order."""
    w0, w1 = run["w0"], run["w1"]
    return [
        sum(hi - lo for lo, hi in R.busy_intervals(ops, w0, w1)) / 1e9
        for _, ops in sorted(run["trace"].chips.items())
    ]
