"""ingest (ingest/plane.py): what ``IngestPlane.submit_sync`` holds per call
that is neither the triples, the verify nor the herder (``submit_s`` less
``phase_s`` ``collect``, ``verify`` and ``herder``, over ``submitted``): the
gate and the plane's own bookkeeping — meters, histograms, the timer, the
span, status delivery."""

from benchmarks.layers import common as C


def read(run):
    try:
        calls = C.counter_delta(run, "ingest", "submitted")
        secs = C.counter_delta(run, "ingest", "submit_s")
        for phase in ("collect", "verify", "herder"):
            secs -= C.counter_delta(run, "ingest", "phase_s", phase)
    except KeyError:  # a program without the counters
        return None
    return secs / calls * 1e6 if calls else None
