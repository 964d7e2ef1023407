#!/usr/bin/env python
"""Offline analysis of the runs' files (no JAX, no chip).

    python benchmarks/tools/series.py windows <run.json> [...]
        what the run metric would have read over every contiguous window of
        10, 20, 30, 40 and 51 s of a long recorded series (step 1.4: the
        choice of run_seconds): min, median, max and the spread of those
        readings at each length.
    python benchmarks/tools/series.py spread <run.json> [...]
        the end-to-end metrics of a set of runs, and for each metric the
        spread (interquartile distance over median) the driver would read.
"""

from __future__ import annotations

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks import stats  # noqa: E402
from benchmarks.stats import Reading  # noqa: E402


def load(path):
    with open(path) as f:
        return json.load(f)


def windows(paths):
    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    root = os.path.dirname(bench_dir)
    bench = load(os.path.join(root, "BENCHMARK.json"))
    for p in paths:
        run = load(p)
        cell = next(w for w in bench["workloads"] if w["name"] == run["workload"])
        traffic = load(os.path.join(bench_dir, "traffic", cell["traffic"] + ".json"))
        readings = [Reading(*r) for r in run["readings"]]
        readings = [r for r in readings if r.start >= run["t_open"]]
        for name, how in traffic["end_to_end"].items():
            print(f"{run['workload']} seed {run['seed']} {name}: {len(readings)} readings over {run['seconds']} s")
            for length in (10, 20, 30, 40, 51):
                vals = stats.windows_of(readings, how, float(length))
                if len(vals) < 4:
                    continue
                med = statistics.median(vals)
                print(
                    "  %2d s: %3d windows  min %.5g  median %.5g  max %.5g  range %.2f %%  iqr %.2f %%"
                    % (length, len(vals), min(vals), med, max(vals), 100 * (max(vals) - min(vals)) / med, 100 * stats.spread(vals))
                )


def spread(paths):
    by_cell = {}
    for p in paths:
        run = load(p)
        if run.get("trace") or run.get("rehearsal") or run.get("control") or "end_to_end" not in run:
            continue
        by_cell.setdefault(run["workload"], []).append(run)
    for cell, runs in sorted(by_cell.items()):
        print(cell, "seeds", [r["seed"] for r in runs])
        for name in runs[0]["end_to_end"]:
            vals = [r["end_to_end"][name] for r in runs]
            line = "  %-18s %s" % (name, " ".join("%.6g" % v for v in vals))
            if len(vals) >= 2:
                line += "  | median %.6g  range %.2f %%" % (
                    statistics.median(vals), 100 * (max(vals) - min(vals)) / statistics.median(vals))
            if len(vals) >= 4:
                line += "  iqr %.2f %%" % (100 * stats.spread(vals))
            print(line)


if __name__ == "__main__":
    {"windows": windows, "spread": spread}[sys.argv[1]](sys.argv[2:])
