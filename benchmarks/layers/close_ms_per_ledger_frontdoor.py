"""apply / commit, on the normal path: the ``ledger.close`` span per ledger;
median over the window's cycles."""

from benchmarks import spans as SP
from benchmarks.layers import common as C


def read(run):
    def one(sp):
        if not SP.named(sp, "ledger.close"):
            return None
        return SP.seconds(sp, "ledger.close")

    return C.ms_per_close(run, one)
