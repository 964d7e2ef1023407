"""apply / commit (ledger/manager.py): distinct source accounts of a closed
set (``accounts`` of the close's ``txset.sort_for_apply``, which the generator
repeats on ``bench.apply_order``); median over the window's ledgers: ~520 of
1,000 under Zipf 0.99 over 10,000 accounts, the set's width where no source
sends twice."""

from benchmarks.layers import skew_common as K


def read(run):
    return K.median_attr(run, "bench.apply_order", "accounts")
