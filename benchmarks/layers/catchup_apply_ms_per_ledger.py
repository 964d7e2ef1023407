"""history (history/catchupsm.py): the ``catchup.apply_ledger`` span — one
replayed ledger from the state machine's post to the hash compared with the
archive's, ``ledger.close`` nested in it; median over the window's ledgers."""

from benchmarks.layers import catchup_common as C


def read(run):
    return C.median_ms(run, "catchup.apply_ledger")
