"""The harness end to end at the rehearsal size, on the CPU: it refuses a
machine without a TPU, a control comes out ``correct: false``, and a new
configuration, traffic mix, cell and per-layer metric are files and entries
only."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def run(root, *args, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout,
    )
    lines = [l for l in p.stdout.splitlines() if l.strip()]
    return p.returncode, lines, p.stderr


def cell(name, seed=11, trace=0, extra=()):
    return ["--workload", name, "--seed", str(seed), "--seconds", "2", "--trace", str(trace), *extra]


def test_refuses_a_machine_without_a_tpu():
    rc, lines, err = run(ROOT, *cell("pay1000.close"))
    assert rc == 2
    assert not any(l.startswith("{") for l in lines)
    assert "no TPU" in err


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks", ignore=shutil.ignore_patterns("__pycache__"))
    rc, lines, _ = run(str(tmp_path), *cell("pay1000.close"))
    assert rc != 0 and not lines


@pytest.mark.parametrize(
    "workload,trace",
    [("pay1000.close", 0), ("pay1000.close", 1), ("pay1000.frontdoor", 1), ("pay5000.sigflush", 0),
     ("pay5000.sigflush", 1)],
)
def test_rehearsal_prints_the_line(workload, trace):
    rc, lines, err = run(ROOT, *cell(workload, trace=trace, extra=["--rehearse-cpu"]))
    assert rc == 0, err[-2000:]
    line = json.loads(lines[-1])
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    key = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in bench[key]}
    assert line["metrics"] and set(line["metrics"]) <= names
    if not trace:
        assert "setup_s" in line["metrics"]
    elif workload == "pay5000.sigflush":
        assert line["metrics"]["verifies_per_s_slice_p50"]["value"] > 0


@pytest.mark.parametrize(
    "workload,control,number",
    [
        ("pay5000.sigflush", "accept-invalid", "invalid_lanes_accepted"),
        ("pay5000.sigflush", "refuse-valid", "window_verdicts_false"),
        ("pay1000.close", "drop-tx", "ledger_hashes_differing"),
        ("pay1000.close", "deferred-commit", "durable_lcl_seq_behind"),
        ("pay1000.frontdoor", "drop-tx", "ledger_hashes_differing"),
    ],
)
def test_a_broken_timed_path_is_not_correct(workload, control, number):
    rc, lines, err = run(ROOT, *cell(workload, extra=["--rehearse-cpu", "--control", control]))
    assert rc == 0, err[-2000:]
    line = json.loads(lines[-1])
    assert line["correct"] is False
    failed = [l for l in lines if l.startswith("check ") and "FAILED" in l]
    assert any(number in l for l in failed), lines


def test_a_new_config_traffic_cell_and_layer_metric_are_files_only(tmp_path):
    root = str(tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks", ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "stellar_tpu"), tmp_path / "stellar_tpu")
    before = {
        os.path.relpath(os.path.join(d, f), root): open(os.path.join(d, f), "rb").read()
        for d, _, fs in os.walk(tmp_path / "benchmarks")
        for f in fs
    }
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    # a configuration: its file of sizes
    conf = json.load(open(os.path.join(BENCH, "configs", "pay1000.json")))
    conf["name"] = "pay16"
    conf["rehearsal"]["width"] = 16
    conf["rehearsal"]["accounts"] = 32
    conf["rehearsal"]["node"]["DESIRED_MAX_TX_PER_LEDGER"] = 16
    json.dump(conf, open(tmp_path / "benchmarks" / "configs" / "pay16.json", "w"))
    # a traffic mix: a data file that the general generator reads
    mix = json.load(open(os.path.join(BENCH, "traffic", "full-ledgers.json")))
    mix["name"] = "big-payments"
    mix["params"]["amount"] = 5000
    json.dump(mix, open(tmp_path / "benchmarks" / "traffic" / "big-payments.json", "w"))
    # a per-layer metric: a reader of its own
    (tmp_path / "benchmarks" / "layers" / "closes_in_window.py").write_text(
        "def read(run):\n    return float(len(run['readings']))\n"
    )
    bench["configs"].append(
        {"name": "pay16", "source": "test", "file": "benchmarks/configs/pay16.json", "reduced": [], "why": "test"}
    )
    bench["workloads"].append(
        {"name": "pay16.big", "config": "pay16", "traffic": "big-payments", "chips": 1, "why": "test"}
    )
    bench["end_to_end"][1]["workloads"].append("pay16.big")
    bench["per_layer"].append(
        {"name": "closes_in_window", "unit": "1", "better": "higher", "source": "host_clock",
         "layer": "apply / commit", "moves": "close_p50_ms", "workloads": ["pay16.big"]}
    )
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))
    for trace in (0, 1):
        rc, lines, err = run(root, *cell("pay16.big", trace=trace, extra=["--rehearse-cpu"]))
        assert rc == 0, err[-2000:]
        line = json.loads(lines[-1])
        assert line["correct"] is True
        if trace:
            assert line["metrics"]["closes_in_window"]["value"] > 0
        else:
            assert line["metrics"]["close_p50_ms"]["value"] > 0
    after = {
        p: open(os.path.join(root, p), "rb").read()
        for p in before
        if os.path.exists(os.path.join(root, p))
    }
    assert after == before, "an existing benchmark file was edited"
