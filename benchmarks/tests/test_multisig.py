"""The ``multisig5000`` configuration, its cell and its per-layer readers (PR
26), at the rehearsal size on the CPU: the cell prints a correct line with
every new metric, a broken timed path is not correct, an envelope signed 2
of 5 that is forced past ``check_valid`` counts against the authorisation
guarantee, each new reader reads a known answer and finds nothing in a
program without the spans and counters."""

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
CELL = "multisig5000.close"
NEW = [
    "sig_collect_ms_per_close", "verify_triples_per_tx", "eager_host_verifies_per_close",
    "signer_rows_per_close", "device_flush_ms.close", "lane_fill_pct.close",
]


def run(*args, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", "2147483659",
         "--seconds", "6", "--rehearse-cpu", *args],
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
    checks = {l.split()[1] for l in lines if l.startswith("check ")}
    assert {"txs_authorisation_differs", "signer_rows_off", "ledger_hashes_differing",
            "balances_off_plain_arithmetic", "durable_lcl_seq_behind"} <= checks
    if not trace:
        assert set(line["metrics"]) == {"close_p50_ms", "setup_s"}
        return
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert set(line["metrics"]) == listed and set(NEW) <= listed
    value = lambda name: line["metrics"][name]["value"]  # noqa: E731
    assert value("verify_triples_per_tx") == 3.0
    assert value("eager_host_verifies_per_close") == 0.0
    assert value("device_verify_share_pct.close") == 100.0
    # 96 accounts a close, five signer rows deleted and five written each
    assert value("signer_rows_per_close") == 960.0
    assert value("sig_collect_ms_per_close") > 0 and value("device_flush_ms.close") > 0


def test_a_dropped_transaction_is_not_correct():
    rc, lines, err = run("--trace", "0", "--control", "drop-tx")
    assert rc == 0, err[-2000:]
    assert json.loads(lines[-1])["correct"] is False
    assert any(l.startswith("check ledger_hashes_differing") and "FAILED" in l for l in lines), lines


FORCED = r"""
import json, os, sys, tempfile
from benchmarks.measure import Ctx, find_cell, load_json
from benchmarks.reference import Check
from benchmarks.generators import multisig_closes
import stellar_tpu.xdr as X
from stellar_tpu.herder.txset import TxSetFrame

root = sys.argv[1]
bench = load_json(os.path.join(root, "BENCHMARK.json"))
cell, conf = find_cell(bench, "multisig5000.close")
with tempfile.TemporaryDirectory() as work:
    ctx = Ctx(seed=7, config=load_json(os.path.join(root, conf["file"])),
              traffic=load_json(os.path.join(root, "benchmarks", "traffic", cell["traffic"] + ".json")),
              cell=cell, work=work, rehearsal=True, root=root, seconds=2.0)
    wl = multisig_closes.Workload(ctx)
    try:
        env = X.TransactionEnvelope.from_xdr(wl._sets[0][0])
        env.signatures.pop()  # 2 of 5
        wl._sets[0][0] = env.to_xdr()
        node = wl.node
        refused = not node.ledger_data(node.frames(wl._sets[0])).tx_set.check_valid(node.app)
        TxSetFrame.check_valid = lambda self, app: True  # forced past validation
        wl.step(False)
        wl.finish()
        check = Check()
        attempted, failed = wl.check(check)
    finally:
        wl.close()
print(json.dumps({"refused": refused, "failed": failed, "rows": {r["name"]: r["value"] for r in check.rows}}))
"""


def test_an_envelope_signed_two_of_five_counts_when_forced_past_validation():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run(
        [sys.executable, "-c", FORCED, ROOT], cwd=ROOT, env=env, capture_output=True, text=True, timeout=600
    )
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.splitlines()[-1])
    assert out["refused"] is True
    assert out["rows"]["txs_authorisation_differs"] >= 1 and out["failed"] >= 1
    assert out["rows"]["signer_rows_off"] == 0 and out["rows"]["ledger_hashes_differing"] == 0


# -- the readers, each fed a synthetic run with a known answer ----------------

MAIN = 1


def reader(name):
    return importlib.import_module("benchmarks.layers." + name.replace(".", "_")).read


def run_of(spans=(), readings=(), before=None, after=None):
    return {
        "spans": list(spans), "readings": list(readings), "all_readings": list(readings),
        "counters": {"before": before or {}, "after": after or {}},
    }


READINGS = [Reading(0.0, 1.5, 10), Reading(2.0, 3.5, 10), Reading(4.0, 5.5, 10)]
SPANS = [
    S("txset.validate", 0.0, 0.3, MAIN, None), S("sig.collect", 0.01, 0.05, MAIN, None),
    S("sig.device_flush", 0.06, 0.11, MAIN, None), S("bench.flush_rows", 1.2, 1.2, 0, {"signer_rows": 60000}),
    S("txset.validate", 2.0, 2.3, MAIN, None), S("sig.collect", 2.01, 2.07, MAIN, None),
    S("sig.device_flush", 2.08, 2.15, MAIN, None), S("bench.flush_rows", 3.2, 3.2, 0, {"signer_rows": 100000}),
    S("txset.validate", 4.0, 4.3, MAIN, None), S("sig.collect", 4.01, 4.09, MAIN, None),
    S("sig.device_flush", 4.10, 4.16, MAIN, None), S("bench.flush_rows", 5.2, 5.2, 0, {"signer_rows": 100000}),
]
BEFORE = {"sig_backend": {"items": 45000, "lanes": 49152, "cpu_cutover_items": 10, "wedge_fallback_items": 0,
                          "eager_host_verifies": 4}, "applied_tx": 15000}
AFTER = {"sig_backend": {"items": 90000, "lanes": 98304, "cpu_cutover_items": 10, "wedge_fallback_items": 0,
                         "eager_host_verifies": 10}, "applied_tx": 30000}
CASES = [
    ("sig_collect_ms_per_close", run_of(SPANS, READINGS), 60.0),
    ("signer_rows_per_close", run_of(SPANS, READINGS), 100000.0),
    ("device_flush_ms.close", run_of(SPANS, READINGS), 60.0),
    ("verify_triples_per_tx", run_of(readings=READINGS, before=BEFORE, after=AFTER), 3.0),
    ("eager_host_verifies_per_close", run_of(readings=READINGS, before=BEFORE, after=AFTER), 2.0),
    ("lane_fill_pct.close", run_of(before=BEFORE, after=AFTER), 100.0 * 45000 / 49152),
]


@pytest.mark.parametrize("name,synthetic,want", CASES, ids=[c[0] for c in CASES])
def test_reader_reads_the_known_answer(name, synthetic, want):
    assert reader(name)(synthetic) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_program_without_it(name):
    """The parent: no ``sig.collect``, no ``eager_host_verifies``, no rows
    on ``commit.flush`` — and a generator whose counters are not a node's."""
    old_spans = [S("txset.validate", 0.0, 0.3, MAIN, None), S("commit.flush", 1.0, 1.1, MAIN, None)]
    old = run_of(old_spans, READINGS, {"verifier": {"items": 1}}, {"verifier": {"items": 2}})
    assert reader(name)(old) is None
    assert reader(name)(run_of(readings=READINGS)) is None


def test_entries_name_the_cells_and_layers_the_benchmark_has():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"] for w in bench["workloads"]}
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    for name in NEW:
        m = entries[name]
        assert CELL in m["workloads"] and set(m["workloads"]) <= cells
        assert m["layer"] in layers and m["moves"] == "close_p50_ms"
        assert os.path.exists(os.path.join(BENCH, "layers", name.replace(".", "_") + ".py"))

