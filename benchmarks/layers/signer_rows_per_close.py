"""apply / commit (ledger/accountframe.py, ledger/storebuffer.py): rows of
the ``signers`` table deleted plus inserted by a close's store-buffer flush
(``commit.flush``'s ``signer_rows``, which the generator repeats on
``bench.flush_rows``); median over the window's closes."""

import statistics

from benchmarks import spans as SP


def read(run):
    rows = [s.attrs["signer_rows"] for s in SP.named(run["spans"], "bench.flush_rows")]
    return float(statistics.median(rows)) if rows else None
