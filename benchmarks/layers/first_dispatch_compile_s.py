"""verify pipeline (ops/ed25519.py): of the first dispatches before the
window, the seconds inside the backend's ``compile_or_get_cached`` —
``compile_s`` of the ``first_dispatch`` block: XLA and Mosaic on a
persistent-cache miss, the read and load of the executable on a hit."""

from benchmarks.layers.first_dispatch_s import account


def read(run):
    fd = account(run)
    return None if fd is None else fd["compile_s"]
