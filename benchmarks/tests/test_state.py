"""The ``state1m`` configuration, its cell ``state1m.close`` and its per-layer
readers (PR 41), at the rehearsal size on the CPU (2,000 residents, 48-tx
sets): the cell prints a correct line with every new metric, the control —
one untouched resident altered in the SQL file — reads ``correct: false``,
each new reader reads a known answer from a recorded run and finds nothing in
a program without the span and the blocks, and the entries of
``BENCHMARK.json`` name files that are there.  Tier-1
(``tests/test_state_close.py``) holds the program's side."""

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
CELL = "state1m.close"
NEW = ["entry_cache_hit_pct", "accounts_warm_ms_per_close", "account_rows_loaded_per_close", "bucket_apply_s.setup"]
ROWS = {
    "invariant_violations", "closes_not_invariant_checked", "durable_lcl_seq_behind", "durable_lcl_hash_differs",
    "closed_txs_not_yet_in_txhistory", "txs_not_in_txhistory", "ledger_hashes_differing",
    "anchor_bucket_list_hash_differs", "archive_buckets_off", "touched_accounts_off", "created_accounts_off",
    "untouched_sample_off", "account_rows_off", "balance_sum_off", "fee_pool_off", "result_codes_differing",
    "verdicts_differing",
}


def run(*args, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", "2147483741",
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
    assert line["attempted"] > 0 and line["attempted"] % 48 == 0
    assert ROWS <= {l.split()[1] for l in lines if l.startswith("check ")}
    parts = [l.split()[3].rstrip(":") for l in lines if l.startswith("set-up:") and l.split()[2] == "s"]
    assert parts[:5] == ["keys", "archive", "catch-up", "copy", "sets"]
    if not trace:
        assert set(line["metrics"]) == {"close_p50_ms", "setup_s"}
        return
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert set(NEW) <= listed <= set(line["metrics"])
    value = lambda name: line["metrics"][name]["value"]  # noqa: E731
    # 2,000 residents all fit the cache: a close asks SQL for its 24 new
    # destinations and the invariant plane's 16 sampled accounts
    assert value("account_rows_loaded_per_close") == 40.0
    assert value("entry_cache_hit_pct") == pytest.approx(100.0 * (1 - 40 / 96))
    assert value("accounts_warm_ms_per_close") > 0 and value("bucket_apply_s.setup") > 0
    assert value("device_verify_share_pct.close") == 100.0


def test_the_control_reads_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tools", "forged_state.py"), "--seed", "2147483741", "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.splitlines()[-1])
    assert out["correct"] is False and out["failed"] == 0 and out["altered"]["rows_changed"] == 1
    assert out["caught_by"] == ["balance_sum_off", "untouched_sample_off"]


# -- the readers, each fed a recorded run with a known answer ------------------


def reader(name):
    return importlib.import_module("benchmarks.layers." + name.replace(".", "_")).read


def recorded():
    """Three closes of 5,000: warms of 100 / 200 / 300 ms; 30,000 accounts
    probed, 27,000 asked of SQL; the catch-up applied its buckets in 41.5 s."""
    spans, readings = [], []
    for k, d in enumerate((0.1, 0.2, 0.3)):
        t = 10.0 * k
        spans.append(S("ledger.close", t + 0.5, t + 2.0, 1, None))
        spans.append(S("accounts.warm", t + 0.6, t + 0.6 + d, 1, None))
        readings.append(Reading(t, t + 2.0, 5000))
    cache = lambda asked, loads: {"hits": 7 * asked, "misses": 9, "evictions": loads, "warm_asked": asked,  # noqa: E731
                                  "sql_loads": loads, "lines": 131072, "capacity": 131072}
    history = {"rounds": 1, "bucket_apply_entries": 1000000, "bucket_apply_s": 41.5}
    before = {"entry_cache": cache(40000, 36000), "history": history, "applied_tx": 20000}
    after = {"entry_cache": cache(70000, 63000), "history": history, "applied_tx": 35000}
    return {"spans": spans, "readings": readings, "all_readings": readings, "counters": {"before": before, "after": after}}


@pytest.mark.parametrize("name, want", [
    ("entry_cache_hit_pct", 10.0),
    ("accounts_warm_ms_per_close", 200.0),
    ("account_rows_loaded_per_close", 9000.0),
    ("bucket_apply_s.setup", 41.5),
])
def test_reader_reads_the_known_answer(name, want):
    assert reader(name)(recorded()) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_program_without_the_span_and_the_blocks(name):
    """The parent's run: no ``accounts.warm``, no ``entry_cache`` block, a
    ``history`` block without the bucket apply."""
    run_ = recorded()
    run_["spans"] = [s for s in run_["spans"] if s.name == "ledger.close"]
    for edge in ("before", "after"):
        del run_["counters"][edge]["entry_cache"]
        run_["counters"][edge]["history"] = {"rounds": 1}
    assert reader(name)(run_) is None


def test_entries_and_files():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    conf = next(c for c in bench["configs"] if c["name"] == "state1m")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("state1m", "state-ledgers", 1)
    cfg = json.load(open(os.path.join(ROOT, conf["file"])))
    assert cfg["name"] == "state1m" and cfg["source"] == conf["source"] and cfg["architecture"] is None
    assert sorted(cfg["reduced"]) == sorted(conf["reduced"]) == ["DATABASE", "history"] and len(conf["source"]) <= 200
    assert cfg["accounts"] == 1000000 and cfg["node"]["DESIRED_MAX_TX_PER_LEDGER"] == 5000
    assert cfg["node"]["SIGNATURE_BACKEND"] == "tpu" and list(cfg["node"]["HISTORY"]["archive"]) == ["get"]
    assert set(cfg["guarantees"]) == {"durability", "safety", "determinism", "signatures", "state"}
    traffic = json.load(open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")))
    assert traffic["generator"] == "state_closes" and list(traffic["end_to_end"]) == ["close_p50_ms"]
    assert traffic["params"]["catchup_deadline_s"] > 0 and traffic["params"]["sample"] == 10000
    for f in ("generators/state_closes.py", "reference_state.py", "tools/forged_state.py", "README.state.md"):
        assert os.path.exists(os.path.join(BENCH, f)), f
    e2e = next(m for m in bench["end_to_end"] if m["name"] == "close_p50_ms")
    assert CELL in e2e["workloads"] and e2e["bound"] == 0.2
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [CELL] and callable(reader(name)), name
        assert m["moves"] == ("setup_s" if name.endswith(".setup") else "close_p50_ms")
