"""apply / commit (ledger/manager.py, tx/history.py): the two history
inserts of a close, ``fees.rows`` (the set's rows into ``txfeehistory``)
plus ``apply.rows`` (into ``txhistory``), per close; median over the
window's closes.  With ``commit_sql_ms_per_close``, which holds the COMMIT,
it covers the SQL a close writes outside the entry tables."""

from benchmarks import spans as SP
from benchmarks.layers import common as C


def read(run):
    def one(sp):
        if not SP.named(sp, "fees.rows", "apply.rows"):
            return None
        return SP.seconds(sp, "fees.rows", "apply.rows")

    return C.ms_per_close(run, one)
