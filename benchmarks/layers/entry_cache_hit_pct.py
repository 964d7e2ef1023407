"""apply / commit (ledger/entryframe.py): share of the accounts the window's
closes touched that the decoded-entry cache already held — 100 x (1 -
Δ``sql_loads`` / Δ``warm_asked``) of ``/info`` ``entry_cache``, which the
generator carries into the run's counters: ``warm_asked`` the accounts each
close's bulk warm probed, ``sql_loads`` those it (or a later load) had to ask
SQL for.  Not the cache's ``hits`` / ``misses``: those count loads, and after
the warm every load of the close hits.  None from a program that keeps no
such block."""

from benchmarks.layers.common import counter_delta


def read(run):
    if "warm_asked" not in run["counters"]["before"].get("entry_cache", {}):
        return None
    asked = counter_delta(run, "entry_cache", "warm_asked")
    if asked <= 0:
        return None
    return 100.0 * max(0.0, 1.0 - counter_delta(run, "entry_cache", "sql_loads") / asked)
