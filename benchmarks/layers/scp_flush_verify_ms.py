"""signature backend (overlay/manager.py, crypto/sigbackend.py): what the
overlay's SCP flush spends verifying — ``scp.collect`` (the triples built,
one payload encoded an envelope) plus ``sig.flush`` (the batch through the
scheme seam and the backend) — per flush, median over the window's slots,
milliseconds.  Nothing on a program without ``scp.collect``."""

from benchmarks import spans as SP
from benchmarks.layers import common as C


def read(run):
    def verify_s(sp):
        return SP.seconds(sp, "scp.collect", "sig.flush") if SP.named(sp, "scp.collect") else None

    return C.ms_per_close(run, verify_s)
