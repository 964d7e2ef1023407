"""txset validate + sig flush (ledger/closepipeline.py): what of the verify
the pipeline did not hide — the seconds replayed ledgers waited at the top of
their close for the prefetch that covers their set (``close.pipeline.join``),
per replayed ledger of the window.  None where the replay does not go through
the pipeline."""

from benchmarks import spans as SP


def read(run):
    ledgers = len(SP.named(run["spans"], "catchup.apply_ledger"))
    if not ledgers:
        return None
    return SP.seconds(run["spans"], "close.pipeline.join") * 1e3 / ledgers
