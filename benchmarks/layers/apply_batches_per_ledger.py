"""apply / commit (herder/txset.py ``sort_for_apply``): batches of the
protocol's apply order a closed set fell into (batch *d* holds every
account's *d*-th transaction: the longest chain of the set as it closed;
``batches`` of ``txset.sort_for_apply``, repeated on ``bench.apply_order``);
median over the window's ledgers."""

from benchmarks.layers import skew_common as K


def read(run):
    return K.median_attr(run, "bench.apply_order", "batches")
