"""txset validate + sig flush (herder/txset.py), as the herder asks it on
the front door: the ``txset.validate`` spans of a ledger cycle summed — the
trigger's own check and every ``validate_value`` SCP makes at nomination
and at each ballot step; median over the window's cycles."""

from benchmarks import spans as SP
from benchmarks.layers import common as C


def read(run):
    def one(sp):
        if not SP.named(sp, "txset.validate"):
            return None
        return SP.seconds(sp, "txset.validate")

    return C.ms_per_close(run, one)
