"""ingest (ingest/plane.py): the benchmark's span around submission
(``IngestPlane.submit_sync`` per transaction) per transaction submitted;
median over the window's ledger cycles."""

from benchmarks import spans as SP


def read(run):
    def one(sp):
        sub = SP.named(sp, "bench.submit")
        txs = sum((s.attrs or {}).get("txs", 0) for s in sub)
        return SP.seconds(sub, "bench.submit") / txs if txs else None

    v = SP.per_reading_median(run["spans"], run["readings"], one)
    return None if v is None else v * 1e6
