#!/usr/bin/env python
"""The control of ``catchup64.replay``: one round on an archive with a forged
signature planted in it, which has to FAIL the catch-up.

    python benchmarks/tools/forged_replay.py --seed <n> [--rehearse-cpu]

The cell's own set-up publishes the archive; ``forge_archive``
(``benchmarks/generators/replay.py``) then flips one bit of one payment's
signature in a ledger of the middle of the checkpoint and makes the archive
consistent around it, as a forger would: the header chain verifies and every
set hashes to its header's ``txSetHash``, so that only the signature check at
apply — which the close pipeline's prefetch must never stand in for — can
refuse it.  One round is then stepped as the cell steps it.  The last line is
JSON: ``failed_as_it_should`` is true when the generator raised "the catch-up
... failed", the node stopped at the forged ledger with a hash that is not
the forger's, and the plain reader found exactly one bad signature in an
archive whose chain and sets verify.  Exit code 0 only then.  It is no run of
the benchmark: ``--control`` of ``measure.py`` takes the controls of
``benchmarks/controls.py`` alone, which a new cell may not edit."""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
if "--rehearse-cpu" in sys.argv:
    os.environ["JAX_PLATFORMS"] = "cpu"

CELL = "catchup64.replay"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks import measure
    from benchmarks import reference_replay as RR
    from benchmarks.generators import replay

    bench = measure.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, conf = measure.find_cell(bench, CELL)
    device = measure.device_info(args.rehearse_cpu)
    work = tempfile.mkdtemp(prefix="forged.", dir=os.path.join(ROOT, ".bench_work") if os.path.isdir(os.path.join(ROOT, ".bench_work")) else None)
    ctx = measure.Ctx(
        seed=args.seed, config=measure.load_json(os.path.join(ROOT, conf["file"])),
        traffic=measure.load_json(os.path.join(ROOT, "benchmarks", "traffic", cell["traffic"] + ".json")),
        cell=cell, work=work, rehearsal=args.rehearse_cpu, root=ROOT, seconds=0.0,
    )
    wl = replay.Workload(ctx)
    out = {"device": device, "failed_as_it_should": False}
    try:
        seq = (wl.anchor + 2) // 2
        network_id = hashlib.sha256(wl.passphrase.encode()).digest()
        replay.forge_archive(wl.archive_dir, wl.anchor, network_id, seq, wl.tx_count[seq] // 2)
        ref = RR.replay_archive(wl.archive_dir, wl.anchor, wl.passphrase)
        out.update(forged_ledger=seq, archive={k: ref[k] for k in ("headers_off", "sets_off", "signatures_bad")})
        try:
            wl.step(False)
            out["error"] = "the round ended on the anchor: the forged signature was accepted"
        except RuntimeError as e:
            rnd = wl.round
            out.update(
                error=str(e), stopped_at=rnd.lcl(),
                forged_ledger_hash_is_the_forgers=rnd.hashes.get(seq) == ref["hashes"][seq],
                ledgers_before_equal_the_archives=all(rnd.hashes[s] == ref["hashes"][s] for s in range(2, seq)),
                sig_backend={k: v for k, v in rnd.app.sig_backend.stats().items() if isinstance(v, int)},
                close_pipeline=rnd.app.close_pipeline.stats(),
            )
            out["failed_as_it_should"] = (
                "failed at ledger %d" % seq in str(e)
                and not out["forged_ledger_hash_is_the_forgers"] and out["ledgers_before_equal_the_archives"]
                and out["archive"] == {"headers_off": 0, "sets_off": 0, "signatures_bad": 1}
            )
    finally:
        wl.close()
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps(out, default=str), flush=True)
    return 0 if out["failed_as_it_should"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
