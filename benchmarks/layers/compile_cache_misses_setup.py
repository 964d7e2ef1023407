"""verify pipeline (ops/ed25519.py): buckets whose first dispatch before the
window missed JAX's persistent compilation cache — ``cache_misses`` of the
``first_dispatch`` block: 0 on a warm machine, the bucket count on a
checkout's first run, which is what separates the ledger's
``first_setup_s`` from ``setup_s``."""

from benchmarks.layers.first_dispatch_s import account


def read(run):
    fd = account(run)
    return None if fd is None else fd["cache_misses"]
