"""bench.py contract tests: one process, exactly one JSON line on success,
a non-zero exit and no result line when a leg raises or no TPU is found."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_bench(env_extra, timeout=240):
    # ambient BENCH_* knobs (from manual hardware runs) must not leak in
    env = {k: v for k, v in os.environ.items() if not k.startswith("BENCH_")}
    # the chaos-scenario legs are ~60-90s of multi-node sims — covered by
    # their own suite (tests/test_scenarios.py), not by every bench
    # contract run
    env["BENCH_SCENARIOS"] = "0"
    env["JAX_PLATFORMS"] = "cpu"  # asked for by name: the contract legs
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "bench.py"],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def test_bench_emits_one_json_line_with_close_stage_in_process():
    r = run_bench(
        {
            "BENCH_BATCH": "128",
            "BENCH_CHUNKS": "1",
            "BENCH_ITERS": "1",
            "BENCH_CLOSE_TXS": "50",
            "BENCH_CLOSE_LEDGERS": "2",
        }
    )
    assert r.returncode == 0, r.stderr[-500:]
    lines = [l for l in r.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, r.stdout
    out = json.loads(lines[0])
    assert out["metric"] == "ed25519_verifies_per_sec"
    assert out["value"] > 0
    # the line names the platform it ran on, as JAX reports it
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["kind"] and out["device"]["count"] >= 1
    assert out["libsodium_single_core_per_sec"] > 0
    # the host-stage A/B rides every line; the native keys (and the
    # "native" stage label) appear only when a C toolchain built the
    # extension — the hashlib fallback is a supported configuration, same
    # contract as tests/test_sighash.py
    from stellar_tpu import native

    hs = out["host_stage_us_per_item"]
    assert hs["python_us_per_item"] > 0
    if native.load_sighash() is not None:
        assert hs["native_us_per_item"] > 0
        assert out["host_stage"] == "native"
    else:
        assert out["host_stage"] == "python"
    # the close stage ran in this same process, through the tpu backend
    assert out["ledger_close_txs"] == 50
    assert out["ledger_close_p50_ms"] > 0
    assert out["ledger_close_sig_backend"] == "tpu"
    # phase attribution (stellar_tpu/trace/) rides the BENCH json: the
    # close phases must be present and account for real time
    pb = out["phase_breakdown_ms"]
    for phase in ("close.sig_flush", "close.apply", "close.commit"):
        assert phase in pb, pb
    assert pb["ledger.close"] > 0
    # every close line names its dispatch mode (ISSUE r13): the CPU
    # contract run is unsharded by definition
    assert out["sig_mesh_devices"] == 0
    # boot self-check cost (ISSUE r18) rides every close line so a
    # selfcheck regression is visible without a real restart
    assert out["selfcheck_ms"] >= 0
    # verify-at-ingest admission plane (ISSUE r20): the standing
    # flood-defense leg must shed its whole hint-matching invalid-sig
    # flood at the edge, in full size-trigger batches
    assert out["ingest_rejects_per_sec"] > 0
    assert 0 < out["ingest_batch_occupancy"] <= 1.0
    # state-plane hash pipeline (ISSUE r22): paired host/device legs,
    # a merge wall, and the resolved backend ride every close line
    assert out["bucket_hash_mb_per_sec"]["host"] > 0
    assert out["bucket_hash_mb_per_sec"]["device"] > 0
    assert out["bucket_merge_ms"] >= 0
    assert out["bucket_hash_backend"] in (
        "native", "hashlib", "device-xla", "device-pallas"
    )


def test_bench_refuses_a_platform_nobody_asked_for():
    """No TPU and no explicit JAX_PLATFORMS=cpu: the run fails before any
    leg, says why, and prints no result line — it never benches XLA:CPU
    under a device metric's name by itself."""
    r = run_bench({"JAX_PLATFORMS": ""}, timeout=120)
    assert r.returncode != 0
    assert not r.stdout.strip(), r.stdout
    assert "no TPU" in r.stderr


def test_bench_leg_that_raises_fails_the_run():
    r = run_bench(
        {
            "BENCH_BATCH": "16",
            "BENCH_CHUNKS": "1",
            "BENCH_ITERS": "1",
            "BENCH_HOST_STAGE": "0",
            "BENCH_SCP_ENVS": "0",
            "BENCH_SKIP_CLOSE": "1",
            # two envelopes are below the aggregate scheme's bucket floor:
            # the leg's own "aggregate path must engage" assertion raises
            "BENCH_SCP_AGG_N": "2",
        },
        timeout=120,
    )
    assert r.returncode != 0, r.stdout
    assert not r.stdout.strip(), r.stdout
    assert "aggregate path must engage" in r.stderr


def test_bench_byzantine_flood_leg_direct():
    """The flood leg (ISSUE r12): all-reject rate reported and the verify
    cache provably un-polluted — direct call, small fixture."""
    import bench

    items = bench._scp_envelope_items(64)
    out = bench.bench_byzantine_flood(reps=1, items=items)
    assert out["strict_gate_rejects_per_sec"] > 0
    assert out["n"] == 64
    assert out["cache_latched_invalid"] == 0
    # the send-side survival plane leg (ISSUE r17): shed rate + bounded
    # queue-byte high-water + CRITICAL untouched, on every flood line
    sq = out["sendq"]
    assert sq["sendq_shed_per_sec"] > 0
    assert 0 < sq["sendq_bytes_high_water"] <= sq["cap_bytes"]
    assert sq["critical_sheds"] == 0
    from stellar_tpu import native

    if native.load_sighash() is not None:
        assert out["gate_stage_rejects_per_sec"] > 0
