"""apply / commit (tx/ops_offers.py, tx/ops_payment.py, tx/offerexchange.py):
median ``tx.apply`` span of the window's sampled transactions whose operation
meets the order book — MANAGE_OFFER or PATH_PAYMENT (``tx.apply``'s ``op``,
repeated with the span's length on ``bench.tx_apply_op``), microseconds."""

import statistics

from benchmarks import spans as SP

BOOK = ("MANAGE_OFFER", "PATH_PAYMENT")


def read(run):
    sp = [s.attrs["seconds"] for s in SP.named(run["spans"], "bench.tx_apply_op") if s.attrs["op"] in BOOK]
    return statistics.median(sp) * 1e6 if sp else None
