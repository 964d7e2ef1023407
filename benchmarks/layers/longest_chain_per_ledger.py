"""herder / SCP (herder/txset.py, herder/herder.py): transactions of the
longest per-account sequence chain in a proposed set (``longest_chain`` of the
``txset.validate`` that walked it, which the generator repeats on
``bench.set_chains``); median over the window's ledgers.  The engagement
reader of the skew: ~100 under Zipf 0.99 at 1,000 tx a set, 1 where every
source has one transaction a set."""

from benchmarks.layers import skew_common as K


def read(run):
    return K.median_attr(run, "bench.set_chains", "longest_chain")
