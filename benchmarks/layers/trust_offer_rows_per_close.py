"""apply / commit (ledger/trustframe.py, ledger/offerframe.py,
ledger/storebuffer.py): rows of ``trustlines`` and ``offers`` written or
deleted by a close's store-buffer flush (``commit.flush``'s ``trust_rows`` +
``offer_rows``, repeated on ``bench.flush_rows``); median over the window's
closes.  Nothing on a program that does not count them."""

import statistics

from benchmarks import spans as SP


def read(run):
    rows = [
        (s.attrs.get("trust_rows") or 0) + (s.attrs.get("offer_rows") or 0)
        for s in SP.named(run["spans"], "bench.flush_rows")
        if s.attrs.get("trust_rows") is not None or s.attrs.get("offer_rows") is not None
    ]
    return float(statistics.median(rows)) if rows else None
