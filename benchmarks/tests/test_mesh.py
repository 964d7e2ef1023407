"""The four-chip cell ``pay5000x4.sigflush`` (PR 45), rehearsed on the CPU:
with four forced host devices ``SIG_MESH="auto"`` shards every flush and the
line reads ``correct`` with ``mesh_devices.sigflush`` 4; with one device
``"auto"`` gives an unsharded backend whose verdicts are right and whose run
is not the cell's — ``correct: false``, the check that a silent fallback
fails.  Beside it the readers over hand-made runs, and the entries."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import reduce as R
from benchmarks import spans as SP
from benchmarks import stats
from benchmarks.layers import (
    chip_busy_share_pct, chip_busy_skew_pct, mesh_devices_sigflush, upload_ms_per_flush, verifies_per_s_per_chip,
)

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL, TWIN = "pay5000x4.sigflush", "pay5000.sigflush"
NEW = [
    "mesh_devices.sigflush", "upload_ms_per_flush", "chip_busy_share_pct", "chip_busy_skew_pct",
    "verifies_per_s_per_chip",
]
DEVICE_TRACE = {"verify_kernel_us_per_item", "chip_busy_share_pct", "chip_busy_skew_pct"}


def run(devices: int, *args, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", "2147483693",
         "--seconds", "2", "--rehearse-cpu", *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout,
    )
    return p.returncode, [l for l in p.stdout.splitlines() if l.strip()], p.stderr


def failed_rows(lines):
    return {l.split()[1] for l in lines if l.startswith("check ") and "FAILED" in l}


def test_rehearsal_on_four_devices_prints_a_correct_line():
    rc, lines, err = run(4, "--trace", "1")
    assert rc == 0, err[-2000:]
    line = json.loads(lines[-1])
    assert line["rehearsal"] is True and line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4
    assert any(l.startswith("programs_traced: 0") for l in lines)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    # what comes from a device trace a CPU has not
    assert listed - DEVICE_TRACE <= set(line["metrics"]) <= listed
    value = lambda name: line["metrics"][name]["value"]  # noqa: E731
    assert value("mesh_devices.sigflush") == 4
    assert value("device_verify_share_pct.sigflush") == 100.0
    assert 0 < value("upload_ms_per_flush") < value("device_flush_ms")
    assert value("verifies_per_s_per_chip") > 0


def test_rehearsal_on_one_device_is_not_correct():
    rc, lines, err = run(1, "--trace", "0")
    assert rc == 0, err[-2000:]
    line = json.loads(lines[-1])
    assert line["correct"] is False and line["failed"] == 0
    assert failed_rows(lines) == {"mesh_devices_off"}


def test_entries_and_files():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("pay5000x4", "mesh-flushes", 4)
    assert sum(1 for w in bench["workloads"] if w["chips"] == 4) == 1
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["moves"] == "verifies_per_s"
        assert os.path.exists(os.path.join(BENCH, "layers", name.replace(".", "_") + ".py"))
    # every metric the one-chip twin reports, the four-chip cell reports too
    for m in bench["per_layer"] + bench["end_to_end"]:
        if TWIN in m.get("workloads", []):
            assert CELL in m["workloads"], m["name"]
    conf = json.load(open(os.path.join(BENCH, "configs", "pay5000x4.json")))
    twin = json.load(open(os.path.join(BENCH, "configs", "pay5000.json")))
    assert conf["node"] == {**twin["node"], "SIG_MESH": "auto"}
    assert {k: v for k, v in conf["guarantees"].items() if k != "sharding"} == twin["guarantees"]
    assert conf["rehearsal"]["node"] == twin["rehearsal"]["node"]
    traffic = json.load(open(os.path.join(BENCH, "traffic", "mesh-flushes.json")))
    base = json.load(open(os.path.join(BENCH, "traffic", "ledger-flushes.json")))
    for key in ("params", "end_to_end", "min_warmup_readings", "slice_s"):
        assert traffic[key] == base[key]
    assert set(base["phases"]) | {"ed25519.upload", "ed25519.wait", "ed25519.readback"} == set(traffic["phases"])


def hand_run(chip_busy_ms, mesh_devices=4, uploads=((0.0010, 0.0004), (0.0008,))):
    """Two flushes of 5,000 in a one-second window; ``chip_busy_ms``: each
    chip's one operation; ``uploads``: the upload spans' seconds a flush."""
    readings = [stats.Reading(0.0, 0.5, 5000), stats.Reading(0.5, 1.0, 5000)]
    spans = []
    for r, secs in zip(readings, uploads):
        t = r.start
        for s in secs:
            spans.append(SP.S("ed25519.upload", t, t + s, 0, None))
            t += s + 0.001
    chips = {
        f"/device:TPU:{i}": [R.Op("verify_kernel_pallas.1 s32[1,1024]", 1e8, 1e8 + ms * 1e6)]
        for i, ms in enumerate(chip_busy_ms)
    }
    return {
        "spans": spans, "readings": readings, "all_readings": readings, "window": (0.0, 1.0),
        "trace": R.Trace(chips, 0.0, 1), "w0": 0.0, "w1": 1e9,
        "counters": {"before": {"sig_backend": {}}, "after": {"sig_backend": {"mesh_devices": mesh_devices}}},
    }


def test_readers_over_a_hand_made_run():
    run_ = hand_run([300.0, 300.0, 200.0, 200.0])
    assert mesh_devices_sigflush.read(run_) == 4
    assert upload_ms_per_flush.read(run_) == pytest.approx((1.4 + 0.8) / 2)
    assert chip_busy_share_pct.read(run_) == pytest.approx(25.0)
    assert chip_busy_skew_pct.read(run_) == pytest.approx(40.0)
    assert verifies_per_s_per_chip.read(run_) == pytest.approx(2500.0)


def test_readers_find_nothing_where_there_is_nothing():
    # the parent: no upload span; an unsharded backend counts as one chip; one
    # plane has no skew; a CPU has no plane at all
    run_ = hand_run([300.0], mesh_devices=0, uploads=((), ()))
    assert upload_ms_per_flush.read(run_) is None
    assert verifies_per_s_per_chip.read(run_) == pytest.approx(10000.0)
    assert chip_busy_skew_pct.read(run_) is None and chip_busy_share_pct.read(run_) == pytest.approx(30.0)
    run_ = hand_run([])
    assert chip_busy_share_pct.read(run_) is None and chip_busy_skew_pct.read(run_) is None
    # a chip that got no work shows
    assert chip_busy_skew_pct.read(hand_run([400.0, 400.0, 400.0, 0.0])) == pytest.approx(100.0 * 400 / 300)
