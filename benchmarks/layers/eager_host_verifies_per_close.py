"""signature backend (crypto/keys.py): verifications libsodium did one at a
time on the caller's thread, because the prefetch had not latched them
(``eager_host_verifies`` of the ``sig_backend`` counters), per close of the
window.  ``device_verify_share_pct`` does not see them: it divides by what
the batch paths were handed."""

from benchmarks.layers import common as C


def read(run):
    try:
        eager = C.counter_delta(run, "sig_backend", "eager_host_verifies")
    except KeyError:  # a program without the counter
        return None
    closes = len(run["all_readings"])
    return eager / closes if closes else None
