"""herder / SCP (herder/, scp/): per ledger cycle, trigger + crank time
outside the close (``bench.trigger`` + ``bench.crank`` less ``ledger.close``);
median over the window's cycles."""

from benchmarks import spans as SP
from benchmarks.layers import common as C


def read(run):
    def one(sp):
        if not SP.named(sp, "bench.crank"):
            return None
        return SP.seconds(sp, "bench.trigger", "bench.crank") - SP.seconds(sp, "ledger.close")

    return C.ms_per_close(run, one)
