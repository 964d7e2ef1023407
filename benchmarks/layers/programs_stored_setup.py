"""verify pipeline (ops/ed25519.py, ops/programs.py): buckets whose first
dispatch before the window loaded its lowered program from the program store
instead of tracing and lowering the kernel — ``programs_stored`` of the
``first_dispatch`` block (PR 38): the bucket count on a warm machine, 0 on a
checkout's first run (``programs_exported`` counts those) and wherever the
store cannot be used (``programs_traced``).  The engagement reader of
``first_dispatch_trace_lower_s``: where this reads 0 that one cannot fall."""

from benchmarks.layers.first_dispatch_s import account


def read(run):
    fd = account(run)
    # None from a program that keeps no such count (the parent's block has
    # every sum PR 37 gave it and none of the three of PR 38)
    return None if fd is None else fd.get("programs_stored")
