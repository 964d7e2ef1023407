"""verify pipeline (ops/ed25519.py): of the first dispatches before the
window, the seconds JAX spent tracing the Python program and lowering it to
MLIR (Mosaic's serialisation of the Pallas kernel included) — ``trace_s`` +
``lower_s`` of the ``first_dispatch`` block: what ``jax.export`` or a
shipped lowered program could skip, cache hit or not."""

from benchmarks.layers.first_dispatch_s import account


def read(run):
    fd = account(run)
    return None if fd is None else fd["trace_s"] + fd["lower_s"]
