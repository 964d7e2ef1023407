"""signature backend (crypto/sigbackend.py): share of the overlay caller's
verify items that ran on the device (``sig_backend`` ``caller_items``
``overlay``: device / (device + host)) over the window."""

from benchmarks.layers import common as C


def read(run):
    try:
        device = C.counter_delta(run, "sig_backend", "caller_items", "overlay", "device")
        host = C.counter_delta(run, "sig_backend", "caller_items", "overlay", "host")
    except KeyError:  # a program without the counter, or no overlay batch yet
        return None
    return 100.0 * device / (device + host) if device + host else None
