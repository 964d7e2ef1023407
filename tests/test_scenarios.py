"""Chaos-plane tests (stellar_tpu/scenarios/) — the ISSUE r12 acceptance
matrix: 5 fault classes, small shapes each closing ≥10 ledgers under
tier-1 with the invariant plane all-on, a deterministic seeded replay for
the virtual-clock classes, and the ClosePipeline >1-close backlog
exercised under simulation load (ROADMAP #3's remaining leg).
"""

from __future__ import annotations

import pytest

from stellar_tpu.crypto.keys import verify_cache
from stellar_tpu.scenarios import run_matrix
from stellar_tpu.scenarios.matrix import small_specs


def run_class(cls):
    # the global verify cache persists across tests in one process; a
    # scenario's digest is defined against a cold cache (the replay
    # contract is same-preconditions ⇒ same run)
    verify_cache().clear()
    r = run_matrix(only=[cls])[0]
    assert r.ok, r.failures
    sb = r.scoreboard
    assert sb.ledgers_closed >= 10, sb.to_dict()
    assert sb.invariant_violations == 0
    assert sb.ledgers_agree and sb.final_hash
    assert sb.nomination_rounds > 0 and sb.ballot_rounds > 0
    assert sb.flood_fanout > 0  # consensus actually flooded messages
    return sb


def test_partition_heal_small():
    """Majority/minority split at 2-of-3, lag-polled heal, recovery
    measured — and the healed node's replay drains through ClosePipeline
    as a real >1-ledger backlog (dispatch-ahead prewarm + warm join),
    which is the LoadGenerator backlog shape doing its job."""
    sb = run_class("partition_heal")
    assert sb.recovery_ms is not None and sb.recovery_ms > 0
    assert sb.pipeline["dispatched"] >= 1, sb.pipeline
    assert sb.pipeline["joined"] >= 1
    assert sb.pipeline["quarantined"] == 0


def test_byzantine_flood_small():
    """Invalid-sig envelope+tx flood at volume: every envelope fast-
    rejected (strict gate at the overlay batch boundary), the verify
    cache provably un-polluted, the fetch plane un-wedged, and consensus
    closes ≥10 ledgers under the flood."""
    spec = small_specs()["byzantine_flood"]
    flood = spec.faults[0]
    verify_cache().clear()
    from stellar_tpu.scenarios.scenario import Scenario

    r = Scenario(spec).run()
    assert r.ok, r.failures
    sb = r.scoreboard
    assert sb.ledgers_closed >= 10
    assert flood.n_envelopes > 200
    # every flooded envelope rejected and accounted
    assert sb.fast_rejects == flood.n_envelopes
    assert sb.fast_reject_rate_per_sec > 0
    # quarantine-under-flood: zero latched verdicts (the fault's own
    # oracle ran inside Scenario.run; re-assert directly here)
    assert flood.assert_cache_unpolluted() == flood.n_envelopes


def test_slow_lossy_small():
    """Latency + loss/duplicate/reorder/damage on every link: flapped
    connections are re-established by the link doctor and consensus
    grinds forward to ≥10 ledgers."""
    run_class("slow_lossy")


def test_crash_restart_small():
    """3-of-3 quorum: the crash halts the network outright; the restarted
    validator comes back from its on-disk state and consensus recovers
    (recovery time measured from the restart)."""
    sb = run_class("crash_restart")
    assert sb.recovery_ms is not None and sb.recovery_ms > 0


def test_hard_kill_mid_close_small():
    """The storage chaos class (ISSUE r18): a REAL kill, not
    graceful_stop — the in-process storage-fault injector unwinds node
    2's close at the close.pre-commit kill-point (bucket files written
    and renamed, header/LCL/publish rows staged, COMMIT not run) and
    Simulation.kill_node reaps it with no shutdown hooks.  The 3-of-3
    quorum halts; the restart must pass the boot self-check, replay the
    interrupted close from its restored SCP state, and consensus must
    recover inside the floor — with invariants all-on throughout."""
    verify_cache().clear()
    spec = small_specs()["hard_kill_mid_close"]
    kill = spec.faults[0]
    from stellar_tpu.scenarios.scenario import Scenario

    r = Scenario(spec).run()
    assert r.ok, r.failures
    sb = r.scoreboard
    assert sb.ledgers_closed >= 10
    assert sb.invariant_violations == 0
    assert sb.ledgers_agree and sb.final_hash
    # the kill genuinely fired mid-close and the reboot self-checked
    assert kill.n_kills == 1
    assert (kill.selfcheck or {}).get("status") in ("ok", "repaired")
    assert sb.recovery_ms is not None and sb.recovery_ms > 0


def test_catchup_under_load_small():
    """A node partitioned past MAX_SLOTS_TO_REMEMBER while the majority
    closes through checkpoint boundaries under load; it rejoins via
    history-archive catchup (REAL_TIME clock, like the history suite) and
    the buffered replay drains through ClosePipeline."""
    sb = run_class("catchup_load")
    assert sb.recovery_ms is not None
    # pipeline backlog stats are reported, not asserted: how many ledgers
    # buffer during the catchup rounds is real-clock dependent (the
    # deterministic backlog oracle lives in test_partition_heal_small)


def test_byzantine_flood_halfagg_small():
    """The aggregate-scheme flood leg (ISSUE r15): the invalid flood PLUS
    a valid-signature ballot storm (the expensive flood class — every
    storm envelope passes the strict gate and pays full curve math)
    under SCP_SIG_SCHEME="ed25519-halfagg".  The storm buckets verify as
    aggregate MSM checks, liveness holds the same floor as the reference
    flood leg, the verify cache stays clean of BOTH invalid verdicts and
    aggregate-path pollution (assert_cache_unpolluted covers the storm
    keys too), and the fetch plane stays empty."""
    spec = small_specs()["byzantine_flood_halfagg"]
    flood = spec.faults[0]
    verify_cache().clear()
    from stellar_tpu.scenarios.scenario import Scenario

    r = Scenario(spec).run()
    assert r.ok, r.failures
    sb = r.scoreboard
    assert sb.ledgers_closed >= 10
    assert flood.n_storm >= 1000  # the storm actually ran at volume
    agg = sb.aggregate
    assert agg["agg_checks"] >= 10, agg
    assert agg["agg_envelopes"] >= flood.n_storm * 0.9, agg
    assert agg["gate_rejects"] > 0  # the invalid flood hit the gate


def test_flood_scheme_wall_ab():
    """Scheme wall A/B under the SAME mixed flood (storm + invalid),
    measured as crank verify wall — now a cost-REGRESSION gate, not a
    win claim.  History: the pre-review scheme measured 0.5-0.6x here,
    but that margin was subsidized by the mixed-torsion soundness hole
    (REVIEW r15): a sound cofactorless-parity aggregate must prove every
    fresh R prime-order ([L]·P, ~one scalar-mult per envelope — the same
    class of cost libsodium's verify pays), which consumes the MSM's
    savings on a scalar-CPU host.  Measured post-fix: the aggregate wall
    is STABLE (~290 ms/run) while the per-signature wall swings with
    this container's scheduler (±30%, the documented host-noise band),
    so the ratio reads 1.0-1.45x across windows.  Per the repo's
    measurement convention the deterministic oracles (parity, liveness
    floor, cache cleanliness — the other tests in this file) carry the
    evidence; this best-of-4 gate only catches a catastrophic cost
    regression (<= 1.6x, e.g. re-proving cached validator keys every
    flush).  The throughput win is conditional on offloading the
    R-column proof to the TPU batch plane (ROADMAP lead — the verify
    kernel already computes it as verify(A:=R, h:=L, s:=0,
    R:=identity))."""
    from stellar_tpu.scenarios.scenario import Scenario

    # Steadied by PR 32: the two schemes take turns (a burst of the other
    # workers' load falls on both) and the best of four stands for each;
    # what the wall cannot say under six workers the run's own counts do:
    # one aggregate check serves at least MIN_AGG envelopes and next to
    # none of them pay the per-signature fallback on top.
    from stellar_tpu.crypto.aggregate.scheme import HalfAggScheme

    walls = {"ed25519-halfagg": float("inf"), "ed25519": float("inf")}
    for rep in range(4):
        for scheme in walls:
            spec = small_specs()["byzantine_flood_halfagg"]
            spec.scp_sig_scheme = scheme
            suffix = "_persig" if scheme == "ed25519" else ""
            spec.name += "%s_ab%d" % (suffix, rep)
            verify_cache().clear()
            r = Scenario(spec).run()
            assert r.ok, (scheme, r.failures)
            agg = r.scoreboard.aggregate
            walls[scheme] = min(walls[scheme], agg["verify_wall_ms"])
            assert agg["flush_envelopes"] > 3000
            if scheme == "ed25519":
                assert agg["agg_checks"] == agg["agg_envelopes"] == 0
            else:
                assert agg["agg_checks"] * HalfAggScheme.MIN_AGG <= agg["agg_envelopes"], agg
                assert agg["fallback_envelopes"] <= 0.2 * agg["flush_envelopes"], agg
    ratio = walls["ed25519-halfagg"] / walls["ed25519"]
    assert ratio <= 1.6, (
        "aggregate scheme paid %.2fx the per-signature verify wall"
        " at the same flood rate: %s" % (ratio, walls)
    )


def test_slow_reader_small():
    """The overlay survival plane's defining scenario (ISSUE r17): one
    tier peer drains at a fraction of the offered rate.  Its neighbors
    shed FLOOD toward it (never CRITICAL), their per-peer queue bytes
    stay under the configured cap, and the straggler is disconnected
    with ERR_LOAD INSIDE the stall budget — while the consensus floor
    holds across every other node.  All asserted as Scenario verdicts
    (expect_straggler_disconnect / min_flood_sheds /
    assert_high_water_bounded in the spec); re-read here for the
    numbers."""
    verify_cache().clear()
    spec = small_specs()["slow_reader"]
    from stellar_tpu.scenarios.scenario import Scenario

    r = Scenario(spec).run()
    assert r.ok, r.failures
    sb = r.scoreboard
    assert sb.ledgers_closed >= 10  # floor over the NON-straggler nodes
    assert sb.invariant_violations == 0
    assert sb.sendq_straggler_disconnects >= 1
    assert sb.sendq_sheds["flood"] >= 1
    assert sb.sendq_sheds["critical"] == 0
    assert sb.sendq_max_stall_ms >= spec.straggler_stall_ms
    assert sb.sendq_max_stall_ms <= spec.straggler_stall_ms + 400
    assert 0 < sb.sendq_bytes_high_water <= spec.sendq_bytes
    # the straggler lags but agrees on the chain prefix
    assert sb.ledgers_agree and sb.final_hash


def test_overload_storm_small():
    """Saturating tx flood at several times total drain capacity across
    all links: FLOOD sheds at volume, CRITICAL never sheds, the
    queue-byte high-water stays bounded by OVERLAY_SENDQ_BYTES, and the
    liveness floor holds — the exact backpressure the reference's
    unbounded write buffers cannot apply."""
    verify_cache().clear()
    spec = small_specs()["overload_storm"]
    storm = spec.faults[0]
    from stellar_tpu.scenarios.scenario import Scenario

    r = Scenario(spec).run()
    assert r.ok, r.failures
    sb = r.scoreboard
    assert sb.ledgers_closed >= 10
    assert storm.n_storm > 300  # the storm actually ran at volume
    assert sb.sendq_sheds["flood"] >= spec.min_flood_sheds
    assert sb.sendq_sheds["critical"] == 0
    assert 0 < sb.sendq_bytes_high_water <= spec.sendq_bytes
    assert sb.invariant_violations == 0


def test_clock_skew_within_slip_small():
    """The time plane's tolerance contract (ISSUE r19): one node +30s
    static (half the MAX_TIME_SLIP window), another drifting +20ms/s —
    skew the protocol promises to absorb.  The closeTime gates must
    meter NOTHING (max_slip_rejects=0 is a spec verdict) and the floor
    is the undisturbed 1-ledger/s cadence."""
    sb = run_class("clock_skew_within_slip")
    assert sb.slip_rejects_past + sb.slip_rejects_future == 0
    assert sb.ledgers_per_sec >= 0.5


def test_clock_skew_beyond_slip_small():
    """Beyond-slip skew (ISSUE r19): node 2's clock NTP-steps 90s behind,
    so every honest value reads as closeTime-future through its gate —
    the new herder.value.reject-closetime-future meter fires (silent
    drops pre-r19), the node stalls while the 2-of-3 majority holds
    >=0.5 ledgers/s, and after the lag-polled heal the stall probe
    (GET_SCP_STATE replay) rejoins it inside the recovery floor."""
    verify_cache().clear()
    spec = small_specs()["clock_skew_beyond_slip"]
    from stellar_tpu.scenarios.scenario import Scenario

    r = Scenario(spec).run()
    assert r.ok, r.failures
    sb = r.scoreboard
    assert sb.ledgers_closed >= 10  # incl. the skewed node: it rejoined
    assert sb.slip_rejects_future >= 1
    assert sb.ledgers_per_sec >= 0.5
    assert sb.recovery_ms is not None and sb.recovery_ms > 0
    assert sb.recovery_ms <= spec.max_recovery_ms
    assert sb.ledgers_agree and sb.final_hash
    assert sb.invariant_violations == 0


def test_asymmetric_partition_small():
    """One-way isolation (ISSUE r19): node 2 is heard but hears nothing
    (frames toward it dropped pre-MAC — the half-open connection).  The
    links stay up and authenticated the whole window: no flap-driven
    SCP-state replay ever happens, so recovery rides the herder's stall
    probe.  The deaf node stalls, the majority keeps closing, heal
    resumes the same connections and the node replays the missed slots
    inside the recovery floor."""
    verify_cache().clear()
    spec = small_specs()["asymmetric_partition"]
    from stellar_tpu.scenarios.scenario import Scenario

    r = Scenario(spec).run()
    assert r.ok, r.failures
    sb = r.scoreboard
    assert sb.ledgers_closed >= 10
    assert sb.recovery_ms is not None and sb.recovery_ms > 0
    assert sb.recovery_ms <= spec.max_recovery_ms
    assert sb.ledgers_agree and sb.final_hash
    assert sb.invariant_violations == 0
    # the half-open contract: CRITICAL traffic never shed, and no
    # straggler disconnect — the connection itself stayed healthy
    assert sb.sendq_sheds["critical"] == 0


def test_targeted_flood_tier2_small():
    """Targeted tier flood (ISSUE r19): invalid-sig envelope/tx flood +
    drain-capped overload storm aimed ONLY at the tier-2 nodes of a
    3-core + 2-tier ring.  Tier-1's floor is the UNDISTURBED cadence
    (1/s measured; spec floor 0.5), tier-2 sheds FLOOD through the r17
    send queues, no CRITICAL sheds anywhere, the verify cache stays
    clean — all read off the new per-tier scoreboard aggregates."""
    verify_cache().clear()
    spec = small_specs()["targeted_flood_tier2"]
    flood = spec.faults[0]
    from stellar_tpu.scenarios.scenario import Scenario

    r = Scenario(spec).run()
    assert r.ok, r.failures
    sb = r.scoreboard
    t1, t2 = sb.per_tier["tier1"], sb.per_tier["tier2"]
    assert t1["ledgers_closed"] >= 10
    assert t1["ledgers_per_sec"] >= 0.5  # the undisturbed floor
    assert t1["flood_sheds"] == 0  # nothing aimed at the core shed there
    assert t2["flood_sheds"] >= spec.min_flood_sheds
    assert t2["fast_rejects"] == flood.n_envelopes  # every one rejected
    assert t1["critical_sheds"] == 0 and t2["critical_sheds"] == 0
    assert flood.assert_cache_unpolluted() == flood.n_envelopes
    assert sb.ledgers_agree and sb.final_hash  # tier lags, never forks


@pytest.mark.slow  # ~126 s of XLA-CPU compile on the tier-1 host (r21
# budget sweep): the flood/shed/cache oracles run in tier-1 on the cpu
# backend (test_byzantine_flood_small + the halfagg leg), the wedge-latch
# isolation contract in test_ingest/test_backend units — this leg's
# marginal value is the device-shaped compile, which is exactly what
# makes it slow here
def test_byzantine_flood_tpu_small():
    """The tpu-backend flood leg (ROADMAP 6(a) / ISSUE r19): the same
    byzantine flood with SIGNATURE_BACKEND="tpu" and cutover 0, so every
    overlay flush — honest SCP traffic and the invalid flood — rides the
    device batch plane (XLA-CPU oracle in tier-1).  Pins the
    CALLER_OVERLAY wedge-latch contract under flood: the device path is
    genuinely engaged, any stall latch lands on the overlay caller class
    ONLY (a wedged overlay prewarm must never route close flushes onto
    host), and the verdict plane is unchanged — same floors, every
    flooded envelope rejected, cache provably clean."""
    verify_cache().clear()
    spec = small_specs()["byzantine_flood_tpu"]
    flood = spec.faults[0]
    from stellar_tpu.scenarios.scenario import Scenario

    scn = Scenario(spec)
    # capture backend stats before teardown: Scenario.run stops the sim
    stats = {}
    orig_target = scn._target_reached

    def capture_then_check():
        done = orig_target()
        if done:
            for raw, app in scn.sim.nodes.items():
                stats[raw.hex()[:8]] = app.sig_backend.stats()
        return done

    scn._target_reached = capture_then_check
    r = scn.run()
    assert r.ok, r.failures
    sb = r.scoreboard
    assert sb.ledgers_closed >= 10
    assert sb.fast_rejects == flood.n_envelopes
    assert flood.assert_cache_unpolluted() == flood.n_envelopes
    assert stats, "no backend stats captured"
    assert any(s.get("device_calls", 0) > 0 for s in stats.values()), stats
    # the wedge-latch contract stays PER CALLER CLASS under flood: the
    # stats surface reports flips per caller (the mechanics — a latched
    # overlay class never routing close flushes to host — are pinned by
    # test_tx's dedicated wedge suite; a cold-cache compile stall here
    # may legitimately latch an async caller, and the scenario must
    # stay green through it, which r.ok above already proved)
    for s in stats.values():
        assert isinstance(s.get("wedge_latch_flips", {}), dict)


def test_ingest_flood_small():
    """The admission-plane flood leg (ISSUE r20): the LoadGenerator's
    legit stream keeps flowing while an invalid-sig tx flood FROM THE
    EXISTING ROOT ACCOUNT hits node 0's ingest front door at 10x the
    legit arrival rate.  Every flooded tx is shed AT THE EDGE (metered
    ingest.reject.badsig, before check_valid/account loads/fan-out —
    the fault's verify_outcome pins the exact count), the shared verify
    cache stays provably clean of flood verdicts (valid-only latch),
    legit txs keep externalizing through the same front door, and the
    close cadence holds the same floor as the un-flooded shapes."""
    verify_cache().clear()
    spec = small_specs()["ingest_flood"]
    flood = spec.faults[0]
    from stellar_tpu.scenarios.scenario import Scenario

    r = Scenario(spec).run()
    assert r.ok, r.failures
    sb = r.scoreboard
    assert sb.ledgers_closed >= 10
    assert flood.n_txs >= 2000  # the flood genuinely ran at 10x load
    assert sb.ingest_rejects["badsig"] >= spec.min_ingest_sheds
    assert sb.ingest_reject_rate_per_sec > 0  # the per-pod line-rate claim
    assert sb.ingest_admitted > 0  # legit load flowed through the door
    assert sb.invariant_violations == 0
    assert sb.ledgers_agree and sb.final_hash
    assert flood.assert_cache_unpolluted() == flood.n_txs


@pytest.mark.parametrize(
    "cls",
    [
        "partition_heal",
        "byzantine_flood",
        "byzantine_flood_halfagg",
        "ingest_flood",
        "slow_lossy",
        "crash_restart",
        "hard_kill_mid_close",
        "slow_reader",
        "overload_storm",
        "clock_skew_within_slip",
        "clock_skew_beyond_slip",
        "asymmetric_partition",
        "targeted_flood_tier2",
    ],
)
def test_deterministic_replay(cls):
    """ISSUE r12 satellite 3 (and the acceptance's per-shape replay):
    same topology + seed + fault program ⇒ identical ledger hashes AND
    identical scoreboard digest across two runs, for every VIRTUAL-clock
    class — lossy fault rolls come from the scenario's seeded per-link
    RNGs (overlay/loopback.py FaultProfile.apply), never the per-process
    ctor nonce.  Cold verify cache both times (same preconditions).
    catchup_load runs REAL_TIME (archive subprocesses) and is exempt."""
    verify_cache().clear()
    a = run_matrix(only=[cls])[0]
    verify_cache().clear()
    b = run_matrix(only=[cls])[0]
    assert a.ok and b.ok, (a.failures, b.failures)
    assert a.scoreboard.final_hash == b.scoreboard.final_hash
    assert a.scoreboard.final_lcls == b.scoreboard.final_lcls
    assert a.scoreboard.digest() == b.scoreboard.digest()
    # the digest covers the liveness counters too in virtual mode —
    # consensus replayed message-for-message, not just state-for-state
    assert a.scoreboard.nomination_rounds == b.scoreboard.nomination_rounds
    assert a.scoreboard.ballot_rounds == b.scoreboard.ballot_rounds
    assert a.scoreboard.fast_rejects == b.scoreboard.fast_rejects


@pytest.mark.slow
def test_tcp_scale_100():
    """The 100+ node OVER_TCP shape (ISSUE r19 / ROADMAP 6(b')): a
    4-core committee + 96-watcher tier ring over REAL localhost sockets
    — every node must externalize ≥5 ledgers in the chaos window (≥7
    total), chains agree across all 100 nodes, and the per-tier
    aggregates carry the committee/relay split.  This is the
    sendqueue/pack-once-fan-out planes at production-transport scale:
    the run floods tens of thousands of frames through real sockets
    (~10 s wall on this host — the prerequisites PR 13 built are what
    make that possible)."""
    verify_cache().clear()
    r = run_matrix(matrix="big", only=["tcp_scale"])[0]
    assert r.ok, r.failures
    sb = r.scoreboard
    assert len(sb.final_lcls) == 100
    assert min(sb.final_lcls.values()) >= 7  # ≥5 inside the window
    assert sb.ledgers_closed >= 5
    assert sb.ledgers_agree and sb.final_hash
    assert sb.invariant_violations == 0
    assert sb.per_tier["tier1"]["nodes"] == 4
    assert sb.per_tier["tier2"]["nodes"] == 96
    assert sb.per_tier["tier2"]["ledgers_closed"] >= 5
    assert sb.flood_fanout > 10_000  # real fan-out at real-socket scale
    assert sb.sendq_sheds.get("critical", 0) == 0


def test_core_and_tier_topology_externalizes():
    """SURVEY §2.11 core-and-tier quorum ring (the chaos plane's big
    shape): a 3-core mesh + 3-node tier ring externalizes in lockstep —
    consensus traverses the ring through the core."""
    from stellar_tpu.simulation import topologies

    sim = topologies.core_and_tier(core_n=3, tier_n=3)
    sim.start_all_nodes()
    try:
        ok = sim.crank_until(lambda: sim.have_all_externalized(3), 240)
        assert ok, f"core-and-tier stuck at {sim.ledger_nums()}"
        assert sim.all_ledgers_agree()
        assert len(sim.topology_keys) == 6
    finally:
        sim.stop_all_nodes()
        sim.clock.shutdown()


def test_scenarios_cli_exit_codes():
    """`python -m stellar_tpu.scenarios` argument contract (callers
    depend on the nonzero-on-unknown path)."""
    from stellar_tpu.scenarios.__main__ import main

    assert main(["--only", "not_a_fault_class"]) == 2


@pytest.mark.slow
def test_big_matrix_partition_heal():
    """Core-and-tier ring at the big shape (`--matrix big`)."""
    verify_cache().clear()
    r = run_matrix(matrix="big", only=["partition_heal"])[0]
    assert r.ok, r.failures
    assert r.scoreboard.ledgers_closed >= 10
