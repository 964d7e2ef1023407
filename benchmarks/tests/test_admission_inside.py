"""The five readers of admission from inside (PR 51): a known answer for
each from hand-made counters, None for a run whose blocks lack the keys (the
parent's program) and for a window with no submission, the entry and the
file of each found by name; and a CPU rehearsal of each front-door cell,
traced, prints all five and they add up."""

import json
import os
import subprocess
import sys

import pytest
from test_layers_inside import OLD_COUNTERS, reader, run_of

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELLS = ["pay1000.frontdoor", "zipf1000.frontdoor"]

# name -> (unit, better, layer)
NEW = {
    "host_verify_us_per_sig": ("us", "lower", "signature backend"),
    "ingest_collect_us_per_tx": ("us", "lower", "ingest"),
    "ingest_herder_us_per_tx": ("us", "lower", "ingest"),
    "ingest_plane_us_per_tx": ("us", "lower", "ingest"),
    "ingest_entries_per_flush": ("1", "higher", "ingest"),
}


def counters(submitted, submit_s, flushed, flushes, gate, collect, verify, herder, hv_items, hv_s):
    return {
        "sig_backend": {"items": 0, "host_verify": {"calls": hv_items, "items": hv_items, "s": hv_s}},
        "ingest": {
            "submitted": submitted, "submit_s": submit_s, "flushed": flushed, "flushes": flushes,
            "phase_s": {"gate": gate, "collect": collect, "verify": verify, "herder": herder},
        },
        "applied_tx": 0,
    }


# a window of 40,000 submissions, each its own flush but for 10,000 entries
# that rode 2,000 flushes of five: 12 s at the edge, of which 1.2 s the
# triples, 6 s the verify (5 s of it libsodium), 3 s the herder
BEFORE = counters(2000, 1.0, 2000, 2000, 0.1, 0.2, 0.5, 0.25, 2000, 0.4)
AFTER = counters(42000, 13.0, 52000, 44000, 0.9, 1.4, 6.5, 3.25, 52000, 5.4)
WANT = {
    "host_verify_us_per_sig": 5.0 / 50000 * 1e6,
    "ingest_collect_us_per_tx": 1.2 / 50000 * 1e6,
    "ingest_herder_us_per_tx": 3.0 / 50000 * 1e6,
    "ingest_plane_us_per_tx": (12.0 - 1.2 - 6.0 - 3.0) / 40000 * 1e6,
    "ingest_entries_per_flush": 50000 / 42000,
}


@pytest.mark.parametrize("name", list(NEW))
def test_reader_reads_the_known_answer(name):
    assert reader(name)(run_of(before=BEFORE, after=AFTER)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", list(NEW))
def test_reader_finds_nothing_to_read(name):
    # the parent's program: the blocks are there, the keys are not
    assert reader(name)(run_of(before=OLD_COUNTERS, after=OLD_COUNTERS)) is None
    parent = {"sig_backend": {"items": 5, "caller_items": {}}, "ingest": {"submitted": 9, "submit_s": 0.1, "flushes": 9}}
    assert reader(name)(run_of(before=parent, after=parent)) is None
    # a cell without the plane
    assert reader(name)(run_of(before={"sig_backend": {}}, after={"sig_backend": {}})) is None
    # a window with no submission: nothing to divide by
    assert reader(name)(run_of(before=AFTER, after=AFTER)) is None


@pytest.mark.parametrize("name", list(NEW))
def test_entry_and_file(name):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    unit, better, layer = NEW[name]
    (m,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert m == {
        "name": name, "unit": unit, "better": better, "source": "program_counter", "layer": layer,
        "moves": "applied_tx_per_s", "workloads": CELLS,
    }
    assert os.path.exists(os.path.join(BENCH, "layers", name + ".py"))
    assert set(m["workloads"]) <= {w["name"] for w in bench["workloads"]}
    # the layer is one the benchmark already names
    assert layer in {o["layer"] for o in bench["per_layer"] if o["name"] not in NEW}


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_all_five(cell):
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell, "--seed", "2147483951",
         "--seconds", "2", "--trace", "1", "--rehearse-cpu"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads([l for l in p.stdout.splitlines() if l.strip()][-1])
    assert line["rehearsal"] is True and line["correct"] is True and line["failed"] == 0
    assert set(NEW) <= set(line["metrics"])
    value = lambda name: line["metrics"][name]["value"]  # noqa: E731
    # every submission flushes itself, and its one signature is a host verify
    assert value("ingest_entries_per_flush") == 1.0
    assert 0.0 < value("host_verify_us_per_sig")
    inside = (
        value("ingest_collect_us_per_tx") + value("ingest_herder_us_per_tx")
        + value("ingest_plane_us_per_tx") + value("host_verify_us_per_sig")
    )
    # what is missing is the verify phase around libsodium
    assert 0.8 * value("ingest_edge_us_per_tx") < inside <= value("ingest_edge_us_per_tx")
