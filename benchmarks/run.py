#!/usr/bin/env python
"""One run of one benchmark cell.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX: a chip belongs to one process at a time, and
the measuring process (``measure.py``) is the one that holds it.  Its output
is passed through; its last line is the result.  ``--rehearse-cpu`` runs the
same control flow at the configuration's tiny rehearsal size on the CPU and
marks the line as a rehearsal; without it a machine with no TPU exits 2 and
prints no result.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv) -> int:
    if not os.path.exists(os.path.join(ROOT, "stellar_tpu", "__init__.py")):
        print("benchmarks: the program (stellar_tpu/) is not in this checkout", file=sys.stderr)
        return 2
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    if "--rehearse-cpu" in argv:
        env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, os.path.join(HERE, "measure.py"), *argv, "--t0", repr(time.time())]
    child = subprocess.Popen(cmd, cwd=ROOT, env=env)
    try:
        return child.wait()
    except BaseException:
        child.kill()
        child.wait()
        raise


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
