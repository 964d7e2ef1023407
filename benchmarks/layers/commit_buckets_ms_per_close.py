"""apply / commit (ledger/manager.py, bucket/): ``commit.buckets`` per close
(the bucket list's ``add_batch`` and the header's bucket-list hash, inside
``close.commit``); median over the window's closes."""

from benchmarks import spans as SP
from benchmarks.layers import common as C


def read(run):
    def one(sp):
        if not SP.named(sp, "commit.buckets"):
            return None
        return SP.seconds(sp, "commit.buckets")

    return C.ms_per_close(run, one)
