"""apply / commit (ledger/manager.py, herder/txset.py): the close's
``txset.sort_for_apply`` span a ledger — the set laid out in the protocol's
apply order, one sort a batch; median over the window's cycles.  None from a
program that records no such span."""

from benchmarks import spans as SP
from benchmarks.layers import common as C


def read(run):
    def one(sp):
        if not SP.named(sp, "txset.sort_for_apply"):
            return None
        return SP.seconds(sp, "txset.sort_for_apply")

    return C.ms_per_close(run, one)
