"""signature backend (crypto/sigbackend.py): libsodium's own seconds per
signature wherever the backend verified on the host in the window
(``host_verify`` ``s`` / ``items`` of ``/info`` ``sig_backend``): the clock is
read inside the ``sig.host_verify`` span and around the verify loop alone, so
none of the tracer is in it."""

from benchmarks.layers import common as C


def read(run):
    try:
        items = C.counter_delta(run, "sig_backend", "host_verify", "items")
        secs = C.counter_delta(run, "sig_backend", "host_verify", "s")
    except KeyError:  # a program without the counters
        return None
    return secs / items * 1e6 if items else None
