"""verify pipeline (ops/verifier.py): seconds of ``ed25519.upload`` spans a
flush, median over the window's flushes, milliseconds — the host's side of
the host->device copies (one a chunk on one chip, one a shard a chunk under
a mesh), apart from the program's call that ``dispatch_ms`` holds with it.
None from a program that records no such span."""

from benchmarks import spans as SP
from benchmarks.layers import common as C


def read(run):
    if not SP.named(run["spans"], "ed25519.upload"):
        return None
    return C.ms_per_close(run, lambda inside: SP.seconds(inside, "ed25519.upload"))
