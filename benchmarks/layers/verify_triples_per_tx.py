"""signature backend (crypto/sigbackend.py): verifications the backend was
handed per transaction applied, over the window — device items plus the
cutover's and the wedge fallback's (``sig_backend`` counters) over
``applied_tx``: 1.0 where an envelope carries one signature and its source
one key, 3.0 under 3-of-5 signers with distinct hints."""

from benchmarks.layers import common as C


def read(run):
    try:
        applied = C.counter_delta(run, "applied_tx")
        handed = sum(
            C.counter_delta(run, "sig_backend", k)
            for k in ("items", "cpu_cutover_items", "wedge_fallback_items")
        )
    except KeyError:  # a workload without these counters
        return None
    return handed / applied if applied else None
