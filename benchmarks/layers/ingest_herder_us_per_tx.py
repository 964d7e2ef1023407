"""ingest (ingest/plane.py): the flushes' ``Herder.recv_transaction`` calls,
each timed at its call, per entry flushed (``phase_s`` ``herder`` /
``flushed`` of ``/ingest``) over the window: the duplicate probe, the
account's aggregate, ``check_valid``, the balance check, the queue insert."""

from benchmarks.layers import common as C


def read(run):
    try:
        entries = C.counter_delta(run, "ingest", "flushed")
        secs = C.counter_delta(run, "ingest", "phase_s", "herder")
    except KeyError:  # a program without the counters
        return None
    return secs / entries * 1e6 if entries else None
