"""kernels (device trace): the share of the timed window in which a chip
ran an operation, mean over the chips traced — 100 less the device's idle
share, a chip."""

from benchmarks.layers.mesh_common import chip_busy_seconds


def read(run):
    busy = chip_busy_seconds(run)
    window = (run["w1"] - run["w0"]) / 1e9
    if not busy or window <= 0:
        return None
    return 100.0 * sum(busy) / len(busy) / window
