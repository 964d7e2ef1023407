"""ingest (ingest/plane.py): entries a flush took, mean over the window's
flushes (``flushed`` / ``flushes`` of ``/ingest``): 1.0 where every
submission flushes itself (``submit_sync``), up to ``INGEST_BATCH_MAX`` where
the accumulator fills."""

from benchmarks.layers import common as C


def read(run):
    try:
        entries = C.counter_delta(run, "ingest", "flushed")
        flushes = C.counter_delta(run, "ingest", "flushes")
    except KeyError:  # a program without the counters
        return None
    return entries / flushes if flushes else None
