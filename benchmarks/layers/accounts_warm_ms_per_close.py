"""apply / commit (ledger/accountframe.py ``bulk_warm_cache``): the close's
bulk load of every account its set touches, ``accounts.warm`` (chunked
``IN()`` selects of the accounts the entry cache lacks, the signer select
beside each), per close; median over the window's closes.  None where the
program records no such span."""

from benchmarks import spans as SP
from benchmarks.layers import common as C


def read(run):
    if not SP.named(run["spans"], "accounts.warm"):
        return None
    return C.ms_per_close(run, lambda sp: SP.seconds(sp, "accounts.warm") if SP.named(sp, "ledger.close") else None)
