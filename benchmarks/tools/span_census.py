#!/usr/bin/env python
"""One run of one cell (the arguments of ``measure.py``) that also counts the
program's spans: how many each reading of the window recorded, by name, and
what one enabled span costs on this host.  A builder's tool for a
``tracing`` PR: the harness fails a run whose 8,192-span ring drops one, so
what a PR adds per reading has to be known.

    python benchmarks/tools/span_census.py --workload <cell> --seed <n> --seconds 45 --trace 1

The run is ``measure.main`` unchanged except that ``compact`` (called once a
reading with that reading's spans) counts before it compacts.  After the
result line it writes ``chiprun_out/bench/census.<cell>.json`` and prints the
census as one more line: readings, median and maximum spans a reading, the
median a reading by name, and nanoseconds per enabled span (the loop of
``tests/test_trace.py::test_enabled_span_cost_microscale``, best of three).
With ``--keep-trace`` the window's spans (name, start, end, tid; seconds on
``time.monotonic``) are written beside the kept device trace as
``spans.<cell>.s<seed>.json``, to be laid over it offline.
"""

from __future__ import annotations

import collections
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
if "--rehearse-cpu" in sys.argv:
    os.environ["JAX_PLATFORMS"] = "cpu"

from benchmarks import measure  # noqa: E402


def span_cost_ns(n: int = 20000) -> float:
    from stellar_tpu.trace import Tracer

    tr = Tracer(ring_size=1024)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            with tr.span("sig.flush", batch=1, cache_hits=1, misses=0):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    return best * 1e9


def main(argv) -> int:
    per_reading, kept = [], []
    keep = "--keep-trace" in argv  # then the window's spans are kept beside the device trace
    compact = measure.compact

    def counting(spans):
        spans = list(spans)
        per_reading.append(collections.Counter(s.name for s in spans))
        if keep:
            kept.extend((s.name, s.start, s.end, s.tid) for s in spans if s.end is not None)
        return compact(spans)

    measure.compact = counting
    rc = measure.main(argv)
    args = measure.parse(argv)
    readings = per_reading[:-1] or per_reading  # the last call is the drain after the window
    totals = [sum(c.values()) for c in readings]
    names = sorted({n for c in readings for n in c})
    census = {
        "workload": args.workload,
        "readings": len(readings),
        "spans_per_reading_p50": statistics.median(totals) if totals else 0,
        "spans_per_reading_max": max(totals) if totals else 0,
        "by_name_p50": {n: statistics.median(c.get(n, 0) for c in readings) for n in names},
        "span_cost_ns": span_cost_ns(),
    }
    out_dir = args.out or os.path.join(ROOT, "chiprun_out", "bench")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "census.%s.json" % args.workload), "w") as f:
        json.dump(census, f)
    if keep:
        with open(os.path.join(out_dir, "spans.%s.s%d.json" % (args.workload, args.seed)), "w") as f:
            json.dump(kept, f)
    print("census " + json.dumps(census), flush=True)
    return rc


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
