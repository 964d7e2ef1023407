"""signature backend (crypto/sigbackend.py): median ``sig.device_flush``
span, milliseconds: one device flush as ``TpuSigBackend.verify_batch``'s
caller waits for it, timed by the program (the hop to the guarded worker,
staging, dispatch and drain are inside it)."""

import statistics

from benchmarks import spans as SP


def read(run):
    sp = SP.named(run["spans"], "sig.device_flush")
    if not sp:
        return None
    return statistics.median(s.end - s.start for s in sp) * 1e3
