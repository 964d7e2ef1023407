"""ingest (ingest/plane.py): a flush's candidate triples, cache keys and
cache peek per entry flushed (``phase_s`` ``collect`` / ``flushed`` of
``/ingest``) over the window."""

from benchmarks.layers import common as C


def read(run):
    try:
        entries = C.counter_delta(run, "ingest", "flushed")
        secs = C.counter_delta(run, "ingest", "phase_s", "collect")
    except KeyError:  # a program without the counters
        return None
    return secs / entries * 1e6 if entries else None
