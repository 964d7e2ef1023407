"""herder / SCP (herder/herder.py ``_trigger_next_ledger``): building the
proposed set out of the queue — ``herder.trim_invalid`` (the chain walk over
everything pending, and the queue's removal of what it trimmed) plus
``herder.surge`` a ledger; median over the window's cycles.  Both spans are
PR 24's, so a program without the chain counters reports this one too."""

from benchmarks import spans as SP
from benchmarks.layers import common as C


def read(run):
    def one(sp):
        if not SP.named(sp, "herder.trim_invalid", "herder.surge"):
            return None
        return SP.seconds(sp, "herder.trim_invalid", "herder.surge")

    return C.ms_per_close(run, one)
