#!/usr/bin/env python
"""The control of ``zipf1000.frontdoor``, ``chain-order``: a node that applies
a set in XORed-hash order alone, without the per-account batches, has to read
``correct: false``.

    python benchmarks/tools/chain_order.py --seed <n> [--ledgers <k>] [--rehearse-cpu]

The cell's own set-up runs whole.  Then ``TxSetFrame.sort_for_apply`` is
replaced, for the node under test and so for the plain ``cpu`` node that
replays its sets through the same code: the set is ordered by full hash XOR
the contents hash alone, and each account's transactions are laid into that
account's own places in sequence order (an order that broke an account's
sequence would not be quiet: the fee pass raises "bad sequence" and the close
aborts).  ``--ledgers`` cycles are stepped as the cell steps them, and the
cell's check runs.  Both nodes close to the same hashes, every balance and
sequence number is right and every chain gapless — only the plain rule of
``benchmarks/reference_skew.py``, which shares nothing with the program, can
see that batch *d* no longer holds every account's *d*-th transaction:
``apply_order_differs`` >= 1.  The check's rows are printed, then one line of
JSON with ``correct`` (which has to be false) and ``caught_by``.  Exit code 0
only when ``apply_order_differs`` caught it and every other row is at its
limit.  It is no run of the benchmark: ``--control`` of ``measure.py`` takes
the controls of ``benchmarks/controls.py`` alone, which a new cell may not
edit."""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "zipf1000.frontdoor"
CATCHES = {"apply_order_differs"}


def hash_order_alone(txset, tally=None) -> list:
    """What stands in for ``TxSetFrame.sort_for_apply``."""
    xh = int.from_bytes(txset.get_contents_hash(), "big")
    order = sorted(txset.transactions, key=lambda tx: int.from_bytes(tx.get_full_hash(), "big") ^ xh)
    chains: dict = {}
    for tx in order:
        chains.setdefault(tx.source_bytes(), []).append(tx)
    for chain in chains.values():
        chain.sort(key=lambda tx: tx.get_seq_num(), reverse=True)
    if tally is not None:
        tally["accounts"] = len(chains)
        tally["batches"] = max(map(len, chains.values()), default=0)
    return [chains[tx.source_bytes()].pop() for tx in order]


@contextlib.contextmanager
def broken_apply_order():
    from stellar_tpu.herder.txset import TxSetFrame

    kept = TxSetFrame.sort_for_apply
    TxSetFrame.sort_for_apply = hash_order_alone
    try:
        yield
    finally:
        TxSetFrame.sort_for_apply = kept


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--ledgers", type=int, default=6)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    from benchmarks import measure
    from benchmarks.generators import skewed_backlog
    from benchmarks.reference import Check

    bench = measure.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, conf = measure.find_cell(bench, CELL)
    device = measure.device_info(args.rehearse_cpu)
    base = os.path.join(ROOT, ".bench_work")
    work = tempfile.mkdtemp(prefix="chain-order.", dir=base if os.path.isdir(base) else None)
    ctx = measure.Ctx(
        seed=args.seed, config=measure.load_json(os.path.join(ROOT, conf["file"])),
        traffic=measure.load_json(os.path.join(ROOT, "benchmarks", "traffic", cell["traffic"] + ".json")),
        cell=cell, work=work, rehearsal=args.rehearse_cpu, root=ROOT, seconds=0.0,
    )
    wl = skewed_backlog.Workload(ctx)
    try:
        with broken_apply_order():
            for _ in range(args.ledgers):
                wl.step(True)
                wl.drain_spans()
            wl.finish()
            check = Check()
            attempted, failed = wl.check(check)
        check.print()
        off = {r["name"] for r in check.rows if not r["ok"]}
        out = {
            "correct": bool(check.ok and failed == 0), "attempted": int(attempted), "failed": int(failed),
            "caught_by": sorted(off), "window_shape": wl.seen, "device": device,
        }
    finally:
        wl.close()
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0 if off == CATCHES and not out["correct"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
