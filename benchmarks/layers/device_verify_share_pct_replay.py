"""signature backend (crypto/sigbackend.py): share of the window's
verifications that reached the device, over all the rounds' nodes (the
generator sums the ``sig_backend`` counters of the nodes it let go)."""

from benchmarks.layers import common as C


def read(run):
    try:
        return C.device_verify_share_pct(run)
    except KeyError:  # a run without the backend's counters
        return None
