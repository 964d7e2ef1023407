"""``zipf1000.frontdoor`` (PR 49), rehearsed on the CPU: the line reads
``correct`` with every row of the plain reference at its limit and the six
new readers in it; the ``chain-order`` control reads ``correct: false`` by
``apply_order_differs`` alone; the readers over a canned run and over one
without the counters; the entries and the files."""

import json
import os
import subprocess
import sys

import pytest
from test_layers_inside import MAIN, reader, run_of

from benchmarks import reference_skew as RS
from benchmarks.spans import S
from benchmarks.stats import Reading

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL, CONTROL = "zipf1000.frontdoor", "pay1000.frontdoor"
NEW = [
    "longest_chain_per_ledger", "source_accounts_per_ledger", "surge_cut_txs_per_ledger",
    "queue_build_ms_per_ledger", "sort_for_apply_ms_per_ledger", "apply_batches_per_ledger",
]


def lines_of(cmd, timeout=600):
    p = subprocess.run(
        [sys.executable, *cmd], cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=timeout,
    )
    return p.returncode, [l for l in p.stdout.splitlines() if l.strip()], p.stderr


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_a_correct_line(trace):
    rc, lines, err = lines_of(
        [os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", "2147483949", "--seconds", "2",
         "--trace", str(trace), "--rehearse-cpu"]
    )
    assert rc == 0, err[-2000:]
    line = json.loads(lines[-1])
    assert line["rehearsal"] is True and line["correct"] is True and line["failed"] == 0
    rows = {l.split()[1]: l for l in lines if l.startswith("check ")}
    assert set(RS.ROWS) <= set(rows) and not any("FAILED" in l for l in rows.values())
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    if trace:
        listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
        # a two-second window holds no whole three-second slice
        assert listed - {"applied_tx_per_s_slice_p50"} <= set(line["metrics"]) <= listed
        value = lambda name: line["metrics"][name]["value"]  # noqa: E731
        # the mechanism is engaged: chains in the proposed and in the closed set
        assert value("longest_chain_per_ledger") >= 3 and value("apply_batches_per_ledger") >= 3
        assert value("source_accounts_per_ledger") < 24 == value("surge_cut_txs_per_ledger")
        assert value("device_verify_share_pct.frontdoor") == 0.0
    else:
        assert set(line["metrics"]) == {"applied_tx_per_s", "setup_s"}


def test_chain_order_control_is_not_correct():
    rc, lines, err = lines_of(
        [os.path.join(BENCH, "tools", "chain_order.py"), "--seed", "2147483950", "--rehearse-cpu"]
    )
    assert rc == 0, err[-2000:]
    out = json.loads(lines[-1])
    assert out["correct"] is False and out["caught_by"] == ["apply_order_differs"]
    (row,) = [l for l in lines if l.startswith("check apply_order_differs")]
    assert int(row.split()[3]) >= 1 and "FAILED" in row


# -- the readers over a canned run ---------------------------------------------------


def cycle(t0, trim, surge, sort, longest, accounts, cut):
    """One ledger cycle at ``t0``: the trigger's trim and surge filter, the
    close's sort, and the generator's three repeats."""
    return [
        S("herder.trigger", t0, t0 + 0.9, MAIN, None),
        S("herder.trim_invalid", t0 + 0.01, t0 + 0.01 + trim, MAIN, None),
        S("herder.surge", t0 + 0.2, t0 + 0.2 + surge, MAIN, None),
        S("bench.surge_cut", t0 + 0.2 + surge, t0 + 0.2 + surge, 0, {"cut": cut}),
        S("txset.validate", t0 + 0.3, t0 + 0.32, MAIN, None),
        S("bench.set_chains", t0 + 0.32, t0 + 0.32, 0, {"accounts": accounts, "longest_chain": longest}),
        S("ledger.close", t0 + 0.4, t0 + 0.8, MAIN, None),
        S("txset.sort_for_apply", t0 + 0.41, t0 + 0.41 + sort, MAIN, None),
        S("bench.apply_order", t0 + 0.41 + sort, t0 + 0.41 + sort, 0, {"accounts": accounts, "batches": longest}),
    ]


CANNED = cycle(10.0, 0.040, 0.010, 0.002, 98, 520, 1000) + cycle(12.0, 0.060, 0.020, 0.004, 131, 498, 1000) \
    + cycle(14.0, 0.050, 0.012, 0.003, 104, 531, 1000)
READINGS = [Reading(10.0, 11.0, 1000), Reading(12.0, 13.0, 1000), Reading(14.0, 15.0, 1000)]
KNOWN = {
    "longest_chain_per_ledger": 104.0, "apply_batches_per_ledger": 104.0, "source_accounts_per_ledger": 520.0,
    "surge_cut_txs_per_ledger": 1000.0, "queue_build_ms_per_ledger": 62.0, "sort_for_apply_ms_per_ledger": 3.0,
}


@pytest.mark.parametrize("name", NEW)
def test_reader_on_a_canned_run(name):
    assert reader(name)(run_of(CANNED, READINGS)) == pytest.approx(KNOWN[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_on_a_run_without_the_counters(name):
    """The parent commit: PR 24's spans, no attribute repeated, no sort span."""
    parent = [s for s in CANNED if not s.name.startswith("bench.") and s.name != "txset.sort_for_apply"]
    got = reader(name)(run_of(parent, READINGS))
    # the queue's build reads two spans the parent records too
    assert got == (pytest.approx(62.0) if name == "queue_build_ms_per_ledger" else None)
    assert reader(name)(run_of(readings=READINGS)) is None


# -- the entries and the files --------------------------------------------------------------


def test_entries_and_files():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert (len(bench["workloads"]), len(bench["configs"])) == (11, 9)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("zipf1000", "skewed-backlog", 1)
    assert sum(1 for w in bench["workloads"] if w["chips"] == 4) == 1
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["moves"] == "applied_tx_per_s"
        assert os.path.exists(os.path.join(BENCH, "layers", name + ".py"))
    # every metric the uniform control reports, the skewed cell reports too
    for m in bench["per_layer"] + bench["end_to_end"]:
        if CONTROL in m.get("workloads", []):
            assert CELL in m["workloads"], m["name"]
    entry = next(c for c in bench["configs"] if c["name"] == "zipf1000")
    conf = json.load(open(os.path.join(ROOT, entry["file"])))
    twin = json.load(open(os.path.join(BENCH, "configs", "pay1000.json")))
    assert entry["reduced"] == list(conf["reduced"]) == ["accounts", "DATABASE"] and entry["source"] == conf["source"]
    for key in ("node", "database", "clock", "as_shipped"):
        assert conf[key] == twin[key]
    assert {k: v for k, v in conf["width"].items() if k != "operation"} == \
        {k: v for k, v in twin["width"].items() if k != "operation"}
    assert {k: v for k, v in conf["guarantees"].items() if k != "order"} == twin["guarantees"]
    assert conf["skew"] == {
        "distribution": "zipfian", "constant": 0.99, "scrambled": True, "applies_to": ["source", "destination"],
    }
    assert conf["accounts"] == 10000 and conf["hot_set_seed"] == 49
    assert conf["rehearsal"] == {"width": 24, "accounts": 240, "node": twin["rehearsal"]["node"]}
    traffic = json.load(open(os.path.join(BENCH, "traffic", "skewed-backlog.json")))
    base = json.load(open(os.path.join(BENCH, "traffic", "tx-backlog.json")))
    for key in ("trigger", "node", "end_to_end", "min_warmup_readings", "phases", "slice_s"):
        assert traffic[key] == base[key]
    shared = ("pending_widths", "ceiling_tx_per_s", "balance", "amount")
    assert {k: traffic["params"][k] for k in shared} == {k: base["params"][k] for k in shared}
    with open(os.path.join(BENCH, "reference_skew.py"), "rb") as a, \
            open(os.path.join(ROOT, "tests", "reference_skew.py"), "rb") as b:
        assert a.read() == b.read()
