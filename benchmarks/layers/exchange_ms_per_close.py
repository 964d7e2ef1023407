"""apply / commit (tx/offerexchange.py): ``op.exchange`` — one span a
conversion through the order book, path payment or arriving offer — summed
over a close; median over the window's closes, milliseconds.  Nothing on a
program without the span."""

from benchmarks import spans as SP
from benchmarks.layers import common as C


def read(run):
    if not SP.named(run["spans"], "op.exchange"):
        return None
    return C.ms_per_close(run, lambda sp: SP.seconds(sp, "op.exchange"))
