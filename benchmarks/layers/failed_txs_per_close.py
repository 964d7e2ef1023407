"""apply / commit (ledger/manager.py, tx/frame.py): transactions of a close
that failed at apply — fee kept, effects unwound through the savepoint
(``apply.serial``'s ``failed``, repeated on ``bench.apply_failed``); median
over the window's closes."""

import statistics

from benchmarks import spans as SP


def read(run):
    failed = [s.attrs["failed"] for s in SP.named(run["spans"], "bench.apply_failed")]
    return float(statistics.median(failed)) if failed else None
