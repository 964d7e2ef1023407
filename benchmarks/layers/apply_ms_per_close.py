"""apply / commit (ledger/manager.py): ``close.fees`` + ``close.apply`` per
close; median over the window's closes."""

from benchmarks import spans as SP
from benchmarks.layers import common as C


def read(run):
    def one(sp):
        if not SP.named(sp, "close.apply"):
            return None
        return SP.seconds(sp, "close.fees", "close.apply")

    return C.ms_per_close(run, one)
