"""txset validate + sig flush (herder/txset.py): ``sig.collect`` per close —
the set's candidate (key, hash, signature) triples gathered for the
prefetch, one readonly account load with its signer rows a transaction and
signatures x keys hint matches — inside ``txset.validate``; median over the
window's closes."""

from benchmarks import spans as SP
from benchmarks.layers import common as C


def read(run):
    def one(sp):
        if not SP.named(sp, "sig.collect"):
            return None
        return SP.seconds(sp, "sig.collect")

    return C.ms_per_close(run, one)
