"""ingest (ingest/plane.py): seconds inside ``IngestPlane.submit_sync`` per
call, from the plane's own counters (``submit_s`` / ``submitted`` of
``/ingest``) over the window: the admission edge as the program times it."""

from benchmarks.layers import common as C


def read(run):
    try:
        calls = C.counter_delta(run, "ingest", "submitted")
        secs = C.counter_delta(run, "ingest", "submit_s")
    except KeyError:  # a program without the counters
        return None
    return secs / calls * 1e6 if calls else None
