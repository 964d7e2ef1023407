"""herder / SCP (scp/): seconds inside ``SCP.receive_envelope`` (the ledger
close it sets off not counted) per envelope handed to SCP, over the window,
from the herder's counters (``/info`` ``scp``: ``receive_s`` / ``to_scp``);
microseconds."""

from benchmarks.layers import common as C


def read(run):
    try:
        n = C.counter_delta(run, "scp", "to_scp")
        s = C.counter_delta(run, "scp", "receive_s")
    except KeyError:  # a program without the counters
        return None
    return s / n * 1e6 if n else None
