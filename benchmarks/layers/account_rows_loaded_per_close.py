"""apply / commit (ledger/accountframe.py): accounts a close had to ask SQL
for because the entry cache had no line of them — Δ``sql_loads`` of ``/info``
``entry_cache`` over the window's closes (each reads the account's row, or
finds none: a CREATE_ACCOUNT's destination).  The engagement reader of the
state that does not fit the cache: ~9,000 a 5,000-tx close over 10^6 accounts,
0 where every account is a line.  None from a program that keeps no such
block."""

from benchmarks.layers.common import counter_delta


def read(run):
    if "entry_cache" not in run["counters"]["before"] or not run["readings"]:
        return None
    closes = counter_delta(run, "applied_tx") / max(1, run["readings"][0].items)
    if closes <= 0:
        return None
    return counter_delta(run, "entry_cache", "sql_loads") / closes
