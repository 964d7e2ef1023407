"""apply / commit (ledger/manager.py, bucket/, database/): ``close.commit``
per close; median over the window's closes."""

from benchmarks import spans as SP
from benchmarks.layers import common as C


def read(run):
    def one(sp):
        if not SP.named(sp, "close.commit"):
            return None
        return SP.seconds(sp, "close.commit")

    return C.ms_per_close(run, one)
