"""txset validate + sig flush (herder/txset.py, ledger/closepipeline.py):
per close, ``txset.validate`` plus ``close.sig_flush`` less the
``close.fees`` nested in it; median over the window's closes."""

from benchmarks import spans as SP
from benchmarks.layers import common as C


def read(run):
    def one(sp):
        if not SP.named(sp, "txset.validate", "close.sig_flush"):
            return None
        return SP.seconds(sp, "txset.validate") + SP.seconds_excluding(
            sp, "close.sig_flush", "close.fees"
        )

    return C.ms_per_close(run, one)
