#!/usr/bin/env python
"""What each chip ran, from a kept device trace (``--keep-trace``).

    python3 benchmarks/tools/chip_planes.py chiprun_out/bench/<run>.xplane.pb

One block a device plane: its operations by name with their count and
seconds, largest first — which chips hold ``verify_kernel_pallas``
operations, and whether the compiler put a collective (an ``all-gather``)
behind them.  The layer readers sum or average over the planes; this prints
them apart.  Reads the file with ``benchmarks/reduce.py`` alone.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks import reduce as R  # noqa: E402


def main(argv) -> int:
    for path in argv:
        trace = R.load(path)
        print(f"{path}: {len(trace.chips)} device plane(s)")
        for plane, ops in sorted(trace.chips.items()):
            by_name: dict = {}
            for o in ops:
                n, s = by_name.get(o.name, (0, 0.0))
                by_name[o.name] = (n + 1, s + (o.end_ns - o.start_ns) / 1e9)
            print(f"  {plane}: {len(ops)} operations")
            for name, (n, s) in sorted(by_name.items(), key=lambda kv: -kv[1][1])[:12]:
                print(f"    {s:10.4f} s {n:8d} x {name}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
