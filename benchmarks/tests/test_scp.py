"""The ``scp4096`` configuration, its cell and its per-layer readers (PR 32),
at the rehearsal size on the CPU (4 + 28 validators, 256 envelopes a slot):
the cell prints a correct line with every new metric, both controls are not
correct, a pool that runs out fails the run, each new reader reads a known
answer and finds nothing in a program without the spans and counters."""

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
CELL = "scp4096.envelopes"
NEW = [
    "scp_flush_verify_ms", "scp_intake_us_per_envelope", "scp_protocol_us_per_envelope",
    "quorum_nodes_scanned_per_envelope", "envelopes_to_scp_per_slot", "device_verify_share_pct.envelopes",
    "slot_close_ms",
]
SHARED = ["device_flush_ms", "lane_fill_pct", "verify_kernel_us_per_item", "host_stage_us_per_item", "dispatch_ms"]
ROWS = {
    "verdicts_differing", "slots_value_differs", "slots_externalized_early", "slots_not_externalized",
    "forged_reaching_scp", "statements_off", "ledger_hashes_differing", "durable_lcl_seq_behind",
    "durable_lcl_hash_differs", "invariant_violations",
}


def run(*args, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", "2147483659",
         "--seconds", "3", "--rehearse-cpu", *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout,
    )
    return p.returncode, [l for l in p.stdout.splitlines() if l.strip()], p.stderr


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_a_correct_line(trace):
    rc, lines, err = run("--trace", str(trace))
    assert rc == 0, err[-2000:]
    line = json.loads(lines[-1])
    assert line["rehearsal"] is True and line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["attempted"] % 256 == 0
    assert ROWS <= {l.split()[1] for l in lines if l.startswith("check ")}
    if not trace:
        assert set(line["metrics"]) == {"verifies_per_s", "setup_s"}
        return
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert listed == set(NEW) | set(SHARED)
    # the kernel's time comes from a device trace, which a CPU has not
    assert listed - {"verify_kernel_us_per_item"} <= set(line["metrics"]) <= listed
    value = lambda name: line["metrics"][name]["value"]  # noqa: E731
    assert value("device_verify_share_pct.envelopes") == 100.0 and value("lane_fill_pct") == 100.0
    # 6 rounds of 32 less the forged before the third core CONFIRM, plus three
    assert 186 <= value("envelopes_to_scp_per_slot") <= 195
    assert value("quorum_nodes_scanned_per_envelope") > 5 and value("scp_protocol_us_per_envelope") > 0
    assert value("scp_flush_verify_ms") >= value("device_flush_ms") > 0
    assert value("scp_intake_us_per_envelope") > 0 and value("slot_close_ms") > 0


@pytest.mark.parametrize("control, row", [("accept-invalid", "forged_reaching_scp"), ("refuse-valid", "verdicts_differing")])
def test_a_broken_verifier_is_not_correct(control, row):
    rc, lines, err = run("--trace", "0", "--control", control)
    assert rc == 0, err[-2000:]
    assert json.loads(lines[-1])["correct"] is False
    failed = {l.split()[1] for l in lines if l.startswith("check ") and "FAILED" in l}
    assert {"verdicts_differing", row} <= failed, lines


EXHAUST = r"""
import os, sys, tempfile
from benchmarks.measure import Ctx, find_cell, load_json
from benchmarks.generators import committee_slots

root = sys.argv[1]
bench = load_json(os.path.join(root, "BENCHMARK.json"))
cell, conf = find_cell(bench, "scp4096.envelopes")
config = load_json(os.path.join(root, conf["file"]))
config["rehearsal"]["node"]["SIGNATURE_BACKEND"] = "cpu"
with tempfile.TemporaryDirectory() as work:
    ctx = Ctx(seed=11, config=config, traffic=load_json(os.path.join(root, "benchmarks", "traffic", cell["traffic"] + ".json")),
              cell=cell, work=work, rehearsal=True, root=root, seconds=2.0)
    wl = committee_slots.Workload(ctx)
    try:
        signed = len(wl.pool)
        for _ in range(signed):
            wl.step(True)  # inside the window nothing is signed
        try:
            wl.step(True)
        except RuntimeError as e:
            print("FAILED AS IT SHOULD after", signed, ":", e)
    finally:
        wl.close()
"""


def test_an_exhausted_pool_fails_the_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run([sys.executable, "-c", EXHAUST, ROOT], cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "FAILED AS IT SHOULD after 6 : the pool of signed slots is exhausted" in p.stdout


# -- the readers, each fed a synthetic run with a known answer ----------------

MAIN = 1


def reader(name):
    return importlib.import_module("benchmarks.layers." + name.replace(".", "_")).read


def counters(to_scp, receive_s, nodes, device, host):
    return {"scp": {"to_scp": to_scp, "receive_s": receive_s, "quorum_nodes_scanned": nodes},
            "sig_backend": {"caller_items": {"overlay": {"device": device, "host": host}}}}


def run_of(spans=(), readings=(), before=None, after=None):
    return {"spans": list(spans), "readings": list(readings), "all_readings": list(readings),
            "counters": {"before": before or {}, "after": after or {}}}


READINGS = [Reading(0.0, 1.5, 4096), Reading(2.0, 3.5, 4096), Reading(4.0, 5.5, 4096)]


def one_slot(t, collect_s, flush_s, deliver_s, recheck_s, receive_s, close_s, to_scp):
    return [
        S("overlay.scp_flush", t + 0.1, t + 0.5, MAIN, None),
        S("scp.collect", t + 0.1, t + 0.1 + collect_s, MAIN, None),
        S("sig.flush", t + 0.2, t + 0.2 + flush_s, MAIN, None),
        S("bench.scp_flush", t + 0.5, t + 0.5, 0, {"envelopes": 4096, "rejected": 64}),
        S("bench.scp_intake", t + 0.5, t + 0.5, 0,
          {"seconds": deliver_s, "to_scp": 0, "dropped_window": 0, "receive_s": 0.0, "close_s": 0.0}),
        S("bench.scp_intake", t + 1.4, t + 1.4, 0,
          {"seconds": recheck_s, "to_scp": to_scp, "dropped_window": 0, "receive_s": receive_s, "close_s": close_s}),
        S("bench.scp_slot", t, t + 1.5, 0, {"slot": int(t), "to_scp": to_scp}),
    ]


SPANS = (
    one_slot(0.0, 0.010, 0.020, 0.2, 0.8, 0.5, 0.010, 3000)
    + one_slot(2.0, 0.020, 0.030, 0.2, 0.8, 0.5, 0.020, 3020)
    + one_slot(4.0, 0.030, 0.040, 0.2, 0.8, 0.5, 0.030, 3040)
)
BEFORE, AFTER = counters(100, 1.0, 1000, 0, 50), counters(9160, 2.812, 3_625_000, 12288, 50)
CASES = [
    ("scp_flush_verify_ms", 50.0),
    ("scp_intake_us_per_envelope", (3 * 1.0 - 1.5 - 0.06) / (3 * 4096) * 1e6),
    ("scp_protocol_us_per_envelope", 200.0),
    ("quorum_nodes_scanned_per_envelope", 400.0),
    ("envelopes_to_scp_per_slot", 3020.0),
    ("device_verify_share_pct.envelopes", 100.0),
    ("slot_close_ms", 20.0),
]


@pytest.mark.parametrize("name,want", CASES, ids=[c[0] for c in CASES])
def test_reader_reads_the_known_answer(name, want):
    assert reader(name)(run_of(SPANS, READINGS, BEFORE, AFTER)) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_program_without_it(name):
    """The parent: ``sig.flush`` and the backend's counters, but no
    ``scp.collect``, no ``/info`` ``scp``, no ``caller_items``, and nothing
    for the generator to repeat."""
    old = [S("sig.flush", 0.2, 0.3, MAIN, None), S("ledger.close", 1.0, 1.1, MAIN, None)]
    plain = {"sig_backend": {"items": 4096, "lanes": 4096}}
    assert reader(name)(run_of(old, READINGS, plain, plain)) is None
    assert reader(name)(run_of(readings=READINGS)) is None


def test_entries_name_the_cells_and_layers_the_benchmark_has():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entries = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    for name in NEW:
        m = entries[name]
        assert m["workloads"] == [CELL] and m["layer"] in layers and m["moves"] == "verifies_per_s"
        assert os.path.exists(os.path.join(BENCH, "layers", name.replace(".", "_") + ".py"))
    for name in SHARED:
        assert entries[name]["workloads"] == ["pay5000.sigflush", CELL]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["config"] == "scp4096" and cell["traffic"] == "committee-slots"
    (e2e,) = [m for m in bench["end_to_end"] if m["name"] == "verifies_per_s"]
    assert e2e["workloads"] == ["pay5000.sigflush", CELL] and e2e["bound"] == 0.06
    config = json.load(open(os.path.join(ROOT, "benchmarks", "configs", "scp4096.json")))
    (conf,) = [c for c in bench["configs"] if c["name"] == "scp4096"]
    assert conf["reduced"] == list(config["reduced"]) == ["DATABASE"] and conf["source"] == config["source"]
    shape = config["committee"]
    assert (shape["core"] + shape["tier"]) * len(config["statements_per_validator_per_slot"]) == 4096
    assert config["envelopes_per_slot"] == 4096 and config["forged_per_slot"] == 64
    assert {"validators", "statements_per_validator", "tx_sets", "delivery", "role", "close_times", "forged"} <= set(config["assumed"])
    assert config["node"]["NODE_IS_VALIDATOR"] is False and config["architecture"] is None
