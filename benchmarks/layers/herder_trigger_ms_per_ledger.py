"""herder / SCP (herder/herder.py): ``herder.trigger`` per ledger less what
other layers do inside it: the ``txset.validate`` spans nested in it and, on
a single-node network, where consensus externalizes inside the trigger, the
``ledger.close``.  What stays is collecting the pending set, trimming,
surge pricing and the SCP rounds; median over the window's cycles."""

from benchmarks import spans as SP
from benchmarks.layers import common as C


def read(run):
    def one(sp):
        if not SP.named(sp, "herder.trigger"):
            return None
        return SP.seconds_excluding(sp, "herder.trigger", "txset.validate", "ledger.close")

    return C.ms_per_close(run, one)
