"""verify pipeline (ops/ed25519.py): median ``ed25519.device_dispatch`` span
(upload + enqueue of one chunk), milliseconds."""

import statistics

from benchmarks import spans as SP


def read(run):
    sp = SP.named(run["spans"], "ed25519.device_dispatch")
    if not sp:
        return None
    return statistics.median(s.end - s.start for s in sp) * 1e3
