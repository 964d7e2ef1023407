"""kernels (device trace): (max - min) / mean of the chips' busy seconds in
the timed window.  0 where every chip worked as long as every other; a chip
that got no work shows here (of four chips, one idle reads 133, one that did
it all 400).  None with one chip."""

from benchmarks.layers.mesh_common import chip_busy_seconds


def read(run):
    busy = chip_busy_seconds(run)
    if len(busy) < 2 or sum(busy) <= 0:
        return None
    return 100.0 * (max(busy) - min(busy)) / (sum(busy) / len(busy))
