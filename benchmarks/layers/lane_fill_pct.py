"""verify pipeline (ops/ed25519.py): items handed to the verifier per
device lane dispatched (``items`` / ``lanes`` of the ``sig_backend``
counters) over the window: how full the padded buckets were."""

from benchmarks.layers import common as C


def read(run):
    try:
        lanes = C.counter_delta(run, "sig_backend", "lanes")
    except KeyError:  # a program without the counter
        return None
    return 100.0 * C.counter_delta(run, "sig_backend", "items") / lanes if lanes else None
