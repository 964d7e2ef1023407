"""verify pipeline (ops/ed25519.py): mean lanes of a device dispatch in the
window (``lanes`` over ``device_calls``): how full the cross-ledger prefetch
makes the device's batches — 4,096 where whole batches leave the carry, 0
where no flush reaches the device (a 1,000-triple set is under the cutover)."""

from benchmarks.layers import common as C


def read(run):
    try:
        calls = C.counter_delta(run, "sig_backend", "device_calls")
        lanes = C.counter_delta(run, "sig_backend", "lanes")
    except KeyError:
        return None
    return lanes / calls if calls else 0.0
