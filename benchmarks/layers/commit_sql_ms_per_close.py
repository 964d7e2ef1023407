"""apply / commit (ledger/manager.py, database/): the SQL of a close's
commit, ``commit.flush`` (the store buffer's batched writes) plus
``commit.sql`` (the COMMIT), per close; median over the window's closes."""

from benchmarks import spans as SP
from benchmarks.layers import common as C


def read(run):
    def one(sp):
        if not SP.named(sp, "commit.sql"):
            return None
        return SP.seconds(sp, "commit.flush", "commit.sql")

    return C.ms_per_close(run, one)
