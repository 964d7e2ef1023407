#!/usr/bin/env python
"""The control of ``state1m.close``: a resident nobody touches, altered in the
node's SQL file, has to read ``correct: false``.

    python benchmarks/tools/forged_state.py --seed <n> [--closes <k>] [--rehearse-cpu]

The cell's own set-up runs whole (keys, archive, catch-up minimal, the copy for
the plain node, the sets).  Then one stroop is added, by ``sqlite3`` alone, to
the balance of the first resident that no prepared set draws; ``--closes`` sets
are closed as the cell closes them (the others are dropped), and the cell's
check runs.  The altered row is in no transaction, no bucket entry the window
writes and no ledger hash, so only what covers the residents at large can see
it: ``balance_sum_off`` (balances + fee pool against the anchor's totalCoins)
always, ``untouched_sample_off`` when the seeded sample of 10,000 holds it (1 %
of the seeds at 10^6 residents; always at the rehearsal's 2,000).  The check's
rows are printed, then one line of JSON with ``correct`` (which has to be
false) and ``caught_by``.  Exit code 0 only when ``correct`` is false, the
balance sum caught it and every other row is 0.  It is no run of the benchmark:
``--control`` of ``measure.py`` takes the controls of ``benchmarks/controls.py``
alone, which a new cell may not edit."""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sqlite3
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
if "--rehearse-cpu" in sys.argv:
    os.environ["JAX_PLATFORMS"] = "cpu"

CELL = "state1m.close"
MAY_CATCH = {"balance_sum_off", "untouched_sample_off"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--closes", type=int, default=4)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks import measure
    from benchmarks.generators import state_closes
    from benchmarks.reference import Check
    from stellar_tpu.crypto import strkey

    bench = measure.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, conf = measure.find_cell(bench, CELL)
    device = measure.device_info(args.rehearse_cpu)
    base = os.path.join(ROOT, ".bench_work")
    work = tempfile.mkdtemp(prefix="forged-state.", dir=base if os.path.isdir(base) else None)
    ctx = measure.Ctx(
        seed=args.seed, config=measure.load_json(os.path.join(ROOT, conf["file"])),
        traffic=measure.load_json(os.path.join(ROOT, "benchmarks", "traffic", cell["traffic"] + ".json")),
        cell=cell, work=work, rehearsal=args.rehearse_cpu, root=ROOT, seconds=0.0,
    )
    wl = state_closes.Workload(ctx)
    try:
        victim = next(i for i in range(wl.n) if i not in wl.drawn)
        aid = strkey.to_account_strkey(wl.pubs[victim].tobytes())
        con = sqlite3.connect(wl.db_path())
        try:
            changed = con.execute("UPDATE accounts SET balance = balance + 1 WHERE accountid = ?", (aid,)).rowcount
            con.commit()
        finally:
            con.close()
        del wl._sets[args.closes:]
        while wl._sets:
            wl.step(True)
            wl.drain_spans()
        wl.finish()
        check = Check()
        attempted, failed = wl.check(check)
        check.print()
        off = {r["name"] for r in check.rows if not r["ok"]}
        out = {
            "correct": bool(check.ok and failed == 0), "attempted": int(attempted), "failed": int(failed),
            "altered": {"resident": victim, "accountid": aid, "rows_changed": changed},
            "caught_by": sorted(off), "device": device,
        }
    finally:
        wl.close()
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps(out), flush=True)
    caught = changed == 1 and not out["correct"] and "balance_sum_off" in off and off <= MAY_CATCH
    return 0 if caught else 1


if __name__ == "__main__":
    raise SystemExit(main())
