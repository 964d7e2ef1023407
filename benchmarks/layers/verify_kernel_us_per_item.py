"""kernels (ops/ed25519_pallas.py): device time of the ``verify_kernel_pallas``
operations in the profiler trace per item the backend was handed in the
traced window (padded lanes not counted)."""

from benchmarks.layers import common as C


def read(run):
    items = C.counter_delta(run, "sig_backend", "items") - C.counter_delta(
        run, "sig_backend", "host_assist_items"
    )
    secs = C.verify_kernel_seconds(run)
    if items <= 0 or secs <= 0:
        return None
    return secs / items * 1e6
