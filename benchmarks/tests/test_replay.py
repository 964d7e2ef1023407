"""The ``catchup64`` configuration, its cell ``catchup64.replay`` and its
per-layer readers (PR 39), at the rehearsal size on the CPU (one checkpoint of
8 ledgers, 24-payment sets): the cell prints a correct line with every new
metric, a forged archive fails the catch-up, each new reader reads a known
answer from a recorded run and finds nothing in a program without the spans
and counters, and the entries of ``BENCHMARK.json`` name files that are
there."""

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
CELL = "catchup64.replay"
NEW = [
    "catchup_round_s", "catchup_fetch_ms_per_round", "catchup_decode_ms_per_round", "catchup_apply_ms_per_ledger",
    "prefetch_join_ms_per_ledger", "device_verify_share_pct.replay", "eager_host_verifies_per_round",
    "prefetch_lanes_per_flush",
]
SETUP = [
    "first_dispatch_s", "first_dispatch_trace_lower_s", "first_dispatch_compile_s", "compile_cache_misses.setup",
    "programs_stored.setup",
]
ROWS = {
    "archive_headers_off", "archive_sets_off", "archive_signatures_bad", "archive_results_off",
    "replayed_hashes_differing", "anchor_hash_differs", "bucket_list_hash_differs", "durable_lcl_behind_or_differs",
    "txhistory_rows_off", "accounts_off_plain_arithmetic", "fee_pool_off_plain_arithmetic", "verify_counts_off",
    "cache_entries_at_round_start", "invariant_violations", "closes_not_invariant_checked",
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
        assert set(line["metrics"]) == {"applied_tx_per_s", "setup_s"}
        return
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert set(NEW) <= listed <= set(NEW) | set(SETUP)
    assert listed <= set(line["metrics"])
    value = lambda name: line["metrics"][name]["value"]  # noqa: E731
    # 5 payment ledgers of 24: the first is verified inline under the
    # cutover, the other four ride one flush of three 32-lane batches
    assert value("device_verify_share_pct.replay") == pytest.approx(100.0 * 96 / 121)
    assert value("prefetch_lanes_per_flush") == 32.0 and value("eager_host_verifies_per_round") == 0.0
    assert value("catchup_round_s") > 0 and value("catchup_apply_ms_per_ledger") > 0
    assert value("catchup_fetch_ms_per_round") > 0 and value("catchup_decode_ms_per_round") > 0
    assert value("prefetch_join_ms_per_ledger") >= 0


def test_a_forged_archive_fails_the_catchup():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tools", "forged_replay.py"), "--seed", "2147483659", "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.splitlines()[-1])
    assert out["failed_as_it_should"] is True and out["stopped_at"] == out["forged_ledger"]
    assert out["archive"] == {"headers_off": 0, "sets_off": 0, "signatures_bad": 1}


# -- the readers, each fed a recorded run with a known answer ------------------

MAIN = 1


def reader(name):
    return importlib.import_module("benchmarks.layers." + name.replace(".", "_")).read


def recorded():
    """Two rounds of three ledgers: fetch 20 ms and 40 ms, decode 5 + chain 1
    ms, ledgers of 100 / 200 / 300 ms, joins of 2 ms and 6 ms in the first
    round; 2,100 of 3,000 verifies on the device in three calls of 3,072
    lanes, 8 eager."""
    spans, readings = [], []
    for r, t in enumerate((0.0, 10.0)):
        spans.append(S("bench.round", t, t + 4.0 + r, 0, None))
        spans.append(S("catchup.round", t + 0.1, t + 3.9, MAIN, None))
        spans.append(S("catchup.fetch", t + 0.1, t + 0.12 + 0.02 * r, MAIN, None))
        spans.append(S("catchup.decode", t + 0.2, t + 0.205, MAIN, None))
        spans.append(S("catchup.verify_chain", t + 0.21, t + 0.211, MAIN, None))
        at = t + 0.3
        for k, d in enumerate((0.1, 0.2, 0.3)):
            spans.append(S("catchup.apply_ledger", at, at + d, MAIN, None))
            spans.append(S("ledger.close", at + 0.001, at + d - 0.001, MAIN, None))
            if r == 0 and k:
                spans.append(S("close.pipeline.join", at + 0.002, at + 0.002 + 0.002 * (2 * k - 1), MAIN, None))
            readings.append(Reading(at, at + d, 500))
            at += d
    sb = lambda items, calls, lanes, cut, eager: {  # noqa: E731
        "items": items, "device_calls": calls, "lanes": lanes, "cpu_cutover_items": cut,
        "wedge_fallback_items": 0, "host_assist_items": 0, "eager_host_verifies": eager,
    }
    before = {"sig_backend": sb(1000, 1, 1024, 100, 2), "replay": {"ledgers": 9, "ledgers_per_round": 3}}
    after = {"sig_backend": sb(3100, 4, 10240, 1000, 10), "replay": {"ledgers": 15, "ledgers_per_round": 3}}
    return {"spans": spans, "readings": readings, "all_readings": readings,
            "counters": {"before": before, "after": after}}


@pytest.mark.parametrize("name, want", [
    ("catchup_round_s", 4.5),
    ("catchup_fetch_ms_per_round", 30.0),
    ("catchup_decode_ms_per_round", 6.0),
    ("catchup_apply_ms_per_ledger", 200.0),
    ("prefetch_join_ms_per_ledger", 8.0 / 6),
    ("device_verify_share_pct.replay", 70.0),
    ("eager_host_verifies_per_round", 4.0),
    ("prefetch_lanes_per_flush", 3072.0),
])
def test_reader_reads_the_known_answer(name, want):
    assert reader(name)(recorded()) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_program_without_the_spans_and_counters(name):
    """The parent's run: a replay that blocks records ``ledger.close`` alone,
    and the generator has no ``history`` block to pass; the backend's counters
    are there and say that nothing reached the device."""
    run_ = recorded()
    run_["spans"] = [s for s in run_["spans"] if s.name in ("bench.round", "ledger.close")]
    for edge in ("before", "after"):
        run_["counters"][edge]["sig_backend"].update(items=0, device_calls=0, lanes=0)
    v = reader(name)(run_)
    if name == "catchup_round_s":
        assert v == pytest.approx(4.5)  # the harness's own span
    elif name in ("device_verify_share_pct.replay", "prefetch_lanes_per_flush"):
        assert v == 0.0
    elif name == "eager_host_verifies_per_round":
        assert v == pytest.approx(4.0)
    else:
        assert v is None
    assert reader(name)({"spans": [], "readings": [], "all_readings": [], "counters": {"before": {}, "after": {}}}) is None


def test_entries_and_files():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    conf = next(c for c in bench["configs"] if c["name"] == "catchup64")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("catchup64", "checkpoint-replay", 1)
    cfg = json.load(open(os.path.join(ROOT, conf["file"])))
    assert cfg["name"] == "catchup64" and cfg["source"] == conf["source"] and cfg["architecture"] is None
    assert sorted(cfg["reduced"]) == sorted(conf["reduced"]) and len(conf["source"]) <= 200
    assert cfg["node"]["SIGNATURE_BACKEND"] == "tpu" and cfg["node"]["DESIRED_MAX_TX_PER_LEDGER"] == 1000
    assert "CHECKPOINT_FREQUENCY" not in cfg["node"] and cfg["accounts"] == 2000
    traffic = json.load(open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")))
    assert traffic["generator"] == "replay" and list(traffic["end_to_end"]) == ["applied_tx_per_s"]
    assert os.path.exists(os.path.join(BENCH, "generators", "replay.py"))
    e2e = next(m for m in bench["end_to_end"] if m["name"] == "applied_tx_per_s")
    assert CELL in e2e["workloads"] and e2e["bound"] == 0.15
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "applied_tx_per_s", name
        assert callable(reader(name))
