"""Bring-up contract (tier-1): chip_smoke.py's CPU rehearsal end to end, its
refusal to run without a TPU, and where the compile cache goes."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env, timeout):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout,
    )


def test_rehearsal_runs_the_whole_flow_on_the_cpu(tmp_path):
    """Producer → publish → tpu-backend replay (prefetched across ledgers) →
    --forcescp restart → own ledger from /tx → kernel leg, through the CLI
    and the admin routes, at 80-tx ledgers."""
    r = _run(["--rehearse-cpu", "--out", str(tmp_path / "out")], dict(os.environ), 600)
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    lines = r.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": last["device"]["count"]},
    }
    assert "REHEARSAL platform=cpu" in lines[-2]
    s = json.loads((tmp_path / "out" / "summary.json").read_text())
    # every replayed signature is accounted for, on the "device": the three
    # root-signed ledgers in one prefetched flush (120: 64 + 64 lanes; alone
    # ledger 2's 20 would be under the cutover), ledger 5 by its own close's
    # flush (its accounts did not exist before: 64 + 16), ledgers 6 + 7 in
    # one prefetched flush (160: 64 + 64 + 32) — nothing verified one at a
    # time at apply, nothing on the watchdog's host path
    assert s["phase_a"]["txs_per_ledger"] == {
        "2": 20, "3": 50, "4": 50, "5": 80, "6": 80, "7": 80,
    }
    b = s["phase_b"]
    sb = b["sig_backend"]
    assert sb["items"] == 360 and sb["device_calls"] == 7 and sb["lanes"] == 368
    assert sb["cpu_cutover_items"] == 0 and sb["eager_host_verifies"] == 0
    assert sb["wedge_fallback_items"] == 0 and sb["wedge_latch_flips"] == {}
    assert sorted(b["buckets"]) == ["16", "32", "64"]
    assert b["host_verify_reasons"] == []  # not one batch under the cutover
    assert "phase_b_warm" not in s  # times the chip's warm start; not rehearsed
    assert b["anchor_hash"] == s["phase_a"]["anchor_hash"]
    assert b["accounts"] == s["phase_a"]["accounts"] == 121
    assert b["txhistory_rows"] == s["phase_a"]["txhistory_rows"] == 360
    assert b["own_ledger"] > s["phase_a"]["anchor"] and b["own_ledger_txs"] == 3
    assert b["invariants"] == {"closes_checked": 6, "total_violations": 0}
    assert set(s["kernel_leg"]["programs"]) == {
        "verify", "device_hash_verify", "sha512", "sha256",
    }
    assert all(s["native_extensions"].values())
    # the big state is gone; logs and the summary stay
    assert not (tmp_path / "out" / "work").exists()
    assert (tmp_path / "out" / "logs" / "replayer.log").exists()


def test_without_a_tpu_and_without_the_flag_it_fails_and_says_why(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _run(["--out", str(tmp_path / "out")], env, 120)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


def test_outside_a_checkout_it_fails(tmp_path):
    """The driver also runs the script alone, in a directory that holds
    nothing else of the repo: non-zero, no result."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path / "chip_smoke.py")
    r = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=dict(os.environ),
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode != 0
    assert "checkout" in r.stderr and '"ok"' not in r.stdout


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_is_placed_by_jax_env_var_only(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR set: the program sets no directory in
    code.  Unset: <checkout>/.jax_cache."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    want = os.path.join(REPO, ".jax_cache")
    if from_env:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    r = subprocess.run(
        [sys.executable, "-c",
         "import jax, stellar_tpu.ops; print(jax.config.jax_compilation_cache_dir)"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0, r.stderr[-500:]
    assert r.stdout.strip() == want
