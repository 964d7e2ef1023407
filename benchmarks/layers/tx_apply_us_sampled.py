"""apply / commit (ledger/applysched.py, tx/frame.py): median ``tx.apply``
span of the window's sampled transactions (one in 64 of every set, by
index), microseconds.  Wall time on the transaction's shard thread: with
several shards sharing the interpreter lock it holds the time the other
shards ran, so it is what a transaction waits, not what it costs a core."""

import statistics

from benchmarks import spans as SP


def read(run):
    sp = SP.named(run["spans"], "tx.apply")
    if not sp:
        return None
    return statistics.median(s.end - s.start for s in sp) * 1e6
