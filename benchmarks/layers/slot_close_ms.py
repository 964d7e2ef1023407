"""apply / commit (ledger/manager.py, from herder/herder.py): the ledger
close a slot's externalization sets off (``close_s`` of the hand-over loop
or the recheck it happened in: ``externalize_value``, or with the close
pipeline on the drain at the end of the queue's sweep); median over the
window's slots, milliseconds."""

from benchmarks import spans as SP
from benchmarks.layers import common as C


def read(run):
    def close_s(sp):
        mine = SP.named(sp, "bench.scp_intake")
        return sum(s.attrs["close_s"] for s in mine) if mine else None

    return C.ms_per_close(run, close_s)
