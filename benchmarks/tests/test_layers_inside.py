"""The readers of the spans and counters the program records from inside
(PR 24), each fed a synthetic run with a known answer, and a run that has
none of them (a program that does not record them yet): None, not an
error, so the line leaves the metric out."""

import importlib

import pytest

from benchmarks.spans import S
from benchmarks.stats import Reading

MAIN, SHARD = 1, 2


def reader(name):
    return importlib.import_module("benchmarks.layers." + name).read


def run_of(spans=(), readings=(), before=None, after=None):
    return {
        "spans": list(spans),
        "readings": list(readings),
        "counters": {"before": before or {}, "after": after or {}},
    }


def close(t0, buckets, flush, sql):
    """One close of 1 s at ``t0``: a commit of 0.5 s whose children last
    ``buckets``, ``flush`` and ``sql`` seconds."""
    c0 = t0 + 0.5
    return [
        S("ledger.close", t0, t0 + 1.0, MAIN, None),
        S("close.commit", c0, t0 + 1.0, MAIN, None),
        S("commit.flush", c0, c0 + flush, MAIN, None),
        S("commit.buckets", c0 + 0.2, c0 + 0.2 + buckets, MAIN, None),
        S("commit.sql", t0 + 1.0 - sql, t0 + 1.0, MAIN, None),
    ]


CLOSES = close(0.0, 0.010, 0.020, 0.030) + close(2.0, 0.030, 0.040, 0.050) + close(4.0, 0.020, 0.030, 0.040)
CLOSE_READINGS = [Reading(0.0, 1.5, 10), Reading(2.0, 3.5, 10), Reading(4.0, 5.5, 10)]

SAMPLED = [
    # (tx.apply seconds, tx.ops seconds) on a shard thread
    S("tx.apply", 0.10, 0.10 + 100e-6, SHARD, None), S("tx.ops", 0.10, 0.10 + 50e-6, SHARD, None),
    S("tx.apply", 0.20, 0.20 + 300e-6, SHARD, None), S("tx.ops", 0.20, 0.20 + 90e-6, SHARD, None),
    S("tx.apply", 2.10, 2.10 + 200e-6, SHARD, None), S("tx.ops", 2.10, 2.10 + 40e-6, SHARD, None),
]

CYCLES = [
    # a ledger cycle: the trigger holds two validations and, on a
    # single-node network, the close; a validation inside the close's
    # thread but outside the trigger must not be taken off
    S("herder.trigger", 10.0, 10.9, MAIN, None),
    S("txset.validate", 10.1, 10.2, MAIN, None),
    S("txset.validate", 10.3, 10.35, MAIN, None),
    S("ledger.close", 10.4, 10.8, MAIN, None),
    S("txset.validate", 10.95, 10.99, MAIN, None),
    S("herder.trigger", 12.0, 12.5, MAIN, None),
    S("txset.validate", 12.1, 12.2, MAIN, None),
    S("herder.trigger", 14.0, 14.7, MAIN, None),
    S("ledger.close", 14.2, 14.6, MAIN, None),
]
CYCLE_READINGS = [Reading(9.5, 11.0, 1000), Reading(11.5, 13.0, 1000), Reading(13.5, 15.0, 1000)]

FLUSHES = [
    S("sig.device_flush", 20.000, 20.017, MAIN, None),
    S("ed25519.drain", 20.005, 20.015, SHARD, None),
    S("ed25519.wait", 20.005, 20.013, SHARD, None),
    S("ed25519.readback", 20.013, 20.015, SHARD, None),
    S("sig.device_flush", 20.020, 20.039, MAIN, None),
    S("ed25519.drain", 20.025, 20.035, SHARD, None),
    S("ed25519.wait", 20.025, 20.029, SHARD, None),
    S("ed25519.readback", 20.029, 20.035, SHARD, None),
    S("sig.device_flush", 20.040, 20.058, MAIN, None),
]

CASES = [
    ("commit_buckets_ms_per_close", run_of(CLOSES, CLOSE_READINGS), 20.0),
    ("commit_sql_ms_per_close", run_of(CLOSES, CLOSE_READINGS), 70.0),
    ("tx_apply_us_sampled", run_of(SAMPLED, CLOSE_READINGS), 200.0),
    ("tx_ops_share_pct", run_of(SAMPLED, CLOSE_READINGS), 30.0),
    # 0.9 - 0.15 - 0.4 = 0.35 / 0.5 - 0.1 = 0.4 / 0.7 - 0.4 = 0.3
    ("herder_trigger_ms_per_ledger", run_of(CYCLES, CYCLE_READINGS), 350.0),
    (
        "ingest_edge_us_per_tx",
        run_of(before={"ingest": {"submitted": 2000, "submit_s": 1.0}},
               after={"ingest": {"submitted": 42000, "submit_s": 13.0}}),
        300.0,
    ),
    ("device_flush_ms", run_of(FLUSHES), 18.0),
    ("drain_wait_share_pct", run_of(FLUSHES), 60.0),
    (
        "lane_fill_pct",
        run_of(before={"sig_backend": {"items": 5000, "lanes": 5120}},
               after={"sig_backend": {"items": 55000, "lanes": 56320}}),
        100.0 * 5000 / 5120,
    ),
]


@pytest.mark.parametrize("name,run,want", CASES, ids=[c[0] for c in CASES])
def test_reader_reads_the_known_answer(name, run, want):
    assert reader(name)(run) == pytest.approx(want)


# what a program without this PR's spans and counters gives the readers
OLD_SPANS = [
    S("ledger.close", 0.0, 1.0, MAIN, None), S("close.commit", 0.5, 1.0, MAIN, None),
    S("apply.group", 0.1, 0.4, SHARD, None), S("txset.validate", 10.1, 10.2, MAIN, None),
    S("ed25519.drain", 20.005, 20.015, SHARD, None), S("bench.verify_batch", 20.0, 20.017, 0, None),
]
OLD_COUNTERS = {"sig_backend": {"items": 5000, "device_calls": 2}, "ingest": {"admitted": 7, "flushes": 7}}


@pytest.mark.parametrize("name", [c[0] for c in CASES])
def test_reader_finds_nothing_to_read(name):
    old = run_of(OLD_SPANS, CLOSE_READINGS, OLD_COUNTERS, OLD_COUNTERS)
    assert reader(name)(old) is None
    assert reader(name)(run_of(readings=CLOSE_READINGS)) is None


def test_every_new_metric_has_its_entry_and_its_file():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"] for w in bench["workloads"]}
    layers = {m["layer"] for m in bench["per_layer"][:18]}
    for name, _run, _want in CASES:
        m = entries[name]
        assert set(m["workloads"]) <= cells and m["workloads"]
        assert m["layer"] in layers  # a layer the benchmark already names
        assert m["source"] in ("program_span", "program_counter")
