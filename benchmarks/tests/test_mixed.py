"""The ``mixed1000`` configuration, its cell and its per-layer readers (PR 30),
at the rehearsal size on the CPU: the cell prints a correct line with every
new metric, a dropped transaction is not correct, a resting offer's amount
altered under the node counts against ``offer_rows_off``, each new reader
reads a known answer and finds nothing in a program without the spans and
counters."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmarks.spans import S
from benchmarks.stats import Reading

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "mixed1000.close"
NEW = [
    "exchange_ms_per_close", "offers_crossed_per_close", "book_rows_per_page", "failed_txs_per_close",
    "trust_offer_rows_per_close", "tx_apply_us_sampled.book",
]
ROWS = {
    "result_codes_differing", "account_rows_off", "trustline_rows_off", "offer_rows_off", "signer_rows_off",
    "ledger_hashes_differing", "balances_off_plain_arithmetic", "durable_lcl_seq_behind", "invariant_violations",
}


def run(*args, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", "2147483659",
         "--seconds", "4", "--rehearse-cpu", *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout,
    )
    return p.returncode, [l for l in p.stdout.splitlines() if l.strip()], p.stderr


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_a_correct_line(trace):
    rc, lines, err = run("--trace", str(trace))
    assert rc == 0, err[-2000:]
    line = json.loads(lines[-1])
    assert line["rehearsal"] is True and line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert ROWS <= {l.split()[1] for l in lines if l.startswith("check ")}
    if not trace:
        assert set(line["metrics"]) == {"close_p50_ms", "setup_s"}
        return
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert set(NEW) <= listed
    # close_tail_ms needs more closes than a rehearsal makes, and the sample
    # of one transaction in 64 rarely meets the book in sets of 40
    assert listed - {"close_tail_ms", "tx_apply_us_sampled.book"} <= set(line["metrics"]) <= listed
    value = lambda name: line["metrics"][name]["value"]  # noqa: E731
    assert value("verify_triples_per_tx") == 1.0
    assert value("exchange_ms_per_close") > 0 and value("offers_crossed_per_close") > 0
    assert value("book_rows_per_page") >= 1 and value("trust_offer_rows_per_close") > 0
    assert value("failed_txs_per_close") == 1.0  # 3 % of 40
    assert value("signer_rows_per_close") > 0


def test_a_dropped_transaction_is_not_correct():
    rc, lines, err = run("--trace", "0", "--control", "drop-tx")
    assert rc == 0, err[-2000:]
    assert json.loads(lines[-1])["correct"] is False
    failed = {l.split()[1] for l in lines if l.startswith("check ") and "FAILED" in l}
    assert {"ledger_hashes_differing", "result_codes_differing"} <= failed, lines


ALTERED = r"""
import json, os, sqlite3, sys, tempfile
from benchmarks.measure import Ctx, find_cell, load_json
from benchmarks.reference import Check
from benchmarks.generators import mixed_closes

root = sys.argv[1]
bench = load_json(os.path.join(root, "BENCHMARK.json"))
cell, conf = find_cell(bench, "mixed1000.close")
with tempfile.TemporaryDirectory() as work:
    ctx = Ctx(seed=7, config=load_json(os.path.join(root, conf["file"])),
              traffic=load_json(os.path.join(root, "benchmarks", "traffic", cell["traffic"] + ".json")),
              cell=cell, work=work, rehearsal=True, root=root, seconds=2.0)
    wl = mixed_closes.Workload(ctx)
    try:
        for _ in range(3):
            wl.step(False)
        wl.finish()
        # a resting offer's amount altered under the node, as a flush that
        # wrote a stale row would leave it
        con = sqlite3.connect(wl.db_path())
        con.execute("UPDATE offers SET amount = amount + 1 WHERE offerid = (SELECT MIN(offerid) FROM offers)")
        con.commit()
        con.close()
        check = Check()
        attempted, failed = wl.check(check)
        notes = wl.notes()
    finally:
        wl.close()
print(json.dumps({"failed": failed, "ok": check.ok, "rows": {r["name"]: r["value"] for r in check.rows},
                  "notes": {k: notes[k] for k in ("shares", "built_to_fail_share", "failed_at_apply_share",
                                                  "offers_resting_at_start", "offers_resting_at_end")}}))
"""


def test_an_altered_offer_row_is_counted():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run(
        [sys.executable, "-c", ALTERED, ROOT], cwd=ROOT, env=env, capture_output=True, text=True, timeout=600
    )
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.splitlines()[-1])
    assert out["rows"]["offer_rows_off"] == 1 and out["ok"] is False
    others = {k: v for k, v in out["rows"].items() if k != "offer_rows_off"}
    assert not any(others.values()), others
    assert abs(sum(out["notes"]["shares"].values()) - 1.0) < 1e-9
    assert out["notes"]["offers_resting_at_start"] > 0 and out["notes"]["offers_resting_at_end"] > 0


# -- the readers, each fed a synthetic run with a known answer ----------------

MAIN = 1


def reader(name):
    return importlib.import_module("benchmarks.layers." + name.replace(".", "_")).read


def run_of(spans=(), readings=()):
    return {"spans": list(spans), "readings": list(readings), "all_readings": list(readings),
            "counters": {"before": {}, "after": {}}}


READINGS = [Reading(0.0, 1.5, 10), Reading(2.0, 3.5, 10), Reading(4.0, 5.5, 10)]


def one_close(t, exchange_s, crossed, pages, rows, failed, trust, offer, book_us):
    """The spans of a close that starts at ``t``: two conversions, one
    sampled offer and one sampled payment."""
    return [
        S("op.exchange", t + 0.1, t + 0.1 + exchange_s / 2, MAIN, None),
        S("bench.exchange", t + 0.2, t + 0.2, 0, {"crossed": crossed, "pages": pages, "rows": rows}),
        S("op.exchange", t + 0.3, t + 0.3 + exchange_s / 2, MAIN, None),
        S("bench.exchange", t + 0.4, t + 0.4, 0, {"crossed": 1, "pages": 1, "rows": 5}),
        S("bench.tx_apply_op", t + 0.5, t + 0.5, 0, {"op": "MANAGE_OFFER", "seconds": book_us / 1e6}),
        S("bench.tx_apply_op", t + 0.6, t + 0.6, 0, {"op": "PAYMENT", "seconds": 1.0}),
        S("bench.apply_failed", t + 1.0, t + 1.0, 0, {"failed": failed}),
        S("bench.flush_rows", t + 1.2, t + 1.2, 0,
          {"signer_rows": 0, "account_rows": 9, "trust_rows": trust, "offer_rows": offer}),
    ]


SPANS = (
    one_close(0.0, 0.010, 3, 1, 5, 28, 300, 100, 400.0)
    + one_close(2.0, 0.020, 5, 2, 25, 30, 310, None, 500.0)
    + one_close(4.0, 0.030, 9, 3, 45, 31, 320, 120, 900.0)
)
CASES = [
    ("exchange_ms_per_close", 20.0),
    ("offers_crossed_per_close", 6.0),
    ("book_rows_per_page", (5 + 25 + 45 + 15) / (1 + 2 + 3 + 3)),
    ("failed_txs_per_close", 30.0),
    ("trust_offer_rows_per_close", 400.0),
    ("tx_apply_us_sampled.book", 500.0),
]


@pytest.mark.parametrize("name,want", CASES, ids=[c[0] for c in CASES])
def test_reader_reads_the_known_answer(name, want):
    assert reader(name)(run_of(SPANS, READINGS)) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_program_without_it(name):
    """The parent: no ``op.exchange``, no ``op`` on ``tx.apply``, no
    ``failed`` on ``apply.serial``, ``commit.flush`` without the two row
    counts — so the generator repeats nothing but the rows it has."""
    old = [
        S("tx.apply", 0.1, 0.2, MAIN, None), S("apply.serial", 0.0, 1.0, MAIN, None),
        S("commit.flush", 1.0, 1.1, MAIN, None),
        S("bench.flush_rows", 1.1, 1.1, 0, {"signer_rows": 0, "account_rows": 9, "trust_rows": None, "offer_rows": None}),
    ]
    assert reader(name)(run_of(old, READINGS)) is None
    assert reader(name)(run_of(readings=READINGS)) is None


def test_entries_name_the_cells_and_layers_the_benchmark_has():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entries = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    for name in NEW:
        m = entries[name]
        assert m["workloads"] == [CELL] and m["layer"] in layers and m["moves"] == "close_p50_ms"
        assert os.path.exists(os.path.join(BENCH, "layers", name.replace(".", "_") + ".py"))
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["config"] == "mixed1000" and cell["traffic"] == "mixed-ledgers"
    config = json.load(open(os.path.join(ROOT, "benchmarks", "configs", "mixed1000.json")))
    assert abs(sum(config["shape"]["shares"].values()) - 1.0) < 1e-9
    assert set(config["reduced"]) == {"accounts", "DATABASE"} and "exchange" in config["guarantees"]
