"""signature backend (crypto/keys.py): verifications libsodium did one at a
time on the applying thread because no prefetch had latched them
(``eager_host_verifies``), per round of the window."""

from benchmarks.layers import catchup_common as CC
from benchmarks.layers import common as C


def read(run):
    rounds = CC.rounds_in_window(run)
    try:
        eager = C.counter_delta(run, "sig_backend", "eager_host_verifies")
    except KeyError:  # a program without the counter
        return None
    return eager / rounds if rounds else None
