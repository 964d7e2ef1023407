"""apply / commit (ledger/offerframe.py, ledger/storebuffer.py): rows read to
serve one five-offer page of the book inside a close — what the SELECT
returned plus the write-back buffer's pending offers walked — over the
window (``op.exchange``'s ``rows`` / ``pages``, repeated on
``bench.exchange``): the overlay merge's over-fetch."""

from benchmarks import spans as SP


def read(run):
    sp = SP.named(run["spans"], "bench.exchange")
    pages = sum(s.attrs["pages"] for s in sp)
    if not pages:
        return None
    return sum(s.attrs["rows"] for s in sp) / pages
