"""herder / SCP (herder/herder.py, herder/txset.py ``surge_pricing_filter``):
transactions the surge filter cut out of a proposed set (``cut`` of
``herder.surge``, repeated on ``bench.surge_cut``: what the ``tx_queue``
counter ``surge_cut`` grew by that ledger); median over the window's ledgers.
With two widths pending the filter cuts a width every ledger; under skew the
cut falls inside chains."""

from benchmarks.layers import skew_common as K


def read(run):
    return K.median_attr(run, "bench.surge_cut", "cut")
