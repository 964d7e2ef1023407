"""Verify-at-ingest admission plane (stellar_tpu/ingest/plane.py, round
20) — the batched front door in front of the herder's tx queue.

Covers the flush semantics (size trigger / deadline timer / shutdown
drain), the verdict-latch contract (one ingest flush makes the herder's
eager check_signature an all-hit, invalid verdicts latch NOTHING), the
edge shed for all-invalid candidate sets, per-caller wedge isolation for
the new CALLER_INGEST class, the per-account token-bucket and fee-based
surge-eviction admission oracles, the replay edge's admission bypass,
and the bit-exact ledger differential with INGEST_BATCH on vs off.
"""

from __future__ import annotations

import pytest

import stellar_tpu.xdr as X
from stellar_tpu.crypto.keys import PubKeyUtils, SecretKey, verify_cache
from stellar_tpu.herder.herder import (
    TX_STATUS_DUPLICATE,
    TX_STATUS_ERROR,
    TX_STATUS_PENDING,
)
from stellar_tpu.ingest import INGEST_STATUS_TRY_AGAIN
from stellar_tpu.main.application import Application
from stellar_tpu.tx import testutils as T
from stellar_tpu.util.clock import VIRTUAL_TIME, VirtualClock


@pytest.fixture
def clock():
    c = VirtualClock(VIRTUAL_TIME)
    yield c
    c.shutdown()


def make_app(clock, instance, **knobs):
    cfg = T.get_test_config(instance)
    cfg.MANUAL_CLOSE = True
    cfg.HTTP_PORT = 0
    for k, v in knobs.items():
        setattr(cfg, k, v)
    app = Application.create(clock, cfg, new_db=True)
    app.start()
    return app


def _root_seq(app) -> int:
    from stellar_tpu.ledger.accountframe import AccountFrame

    root = T.root_key_for(app)
    return AccountFrame.load_account(
        root.get_public_key(), app.database
    ).get_seq_num()


def _payment(app, n, seq, fee=None, corrupt=False):
    """A root-signed create-account tx toward test account ``n``;
    ``corrupt`` flips a signature byte AFTER signing (hint still
    matches, so the candidate triples are non-empty and all-invalid)."""
    frame = T.tx_from_ops(
        app,
        T.root_key_for(app),
        seq,
        [T.create_account_op(T.get_account("ing-%s" % n), 10**9)],
        fee=fee,
    )
    if corrupt:
        sig = bytearray(frame.envelope.signatures[0].signature)
        sig[0] ^= 0xFF
        frame.envelope.signatures[0].signature = bytes(sig)
    return frame


# -- flush semantics --------------------------------------------------------


def test_flush_on_size_trigger(clock):
    """INGEST_BATCH_MAX submissions close the batch synchronously: every
    queued submitter's callback fires with the herder's verdict, and the
    occupancy reads a full batch."""
    app = make_app(
        clock, 60, INGEST_BATCH_MAX=4, INGEST_BATCH_DEADLINE_MS=60_000
    )
    try:
        seq = _root_seq(app)
        got = []
        for i in range(3):
            st = app.ingest.submit(
                _payment(app, i, seq + 1 + i), on_status=got.append
            )
            assert st is None  # queued, undecided
        assert app.ingest.stats()["queued"] == 3 and got == []
        st = app.ingest.submit(_payment(app, 3, seq + 4), on_status=got.append)
        assert st == TX_STATUS_PENDING  # the size trigger flushed
        assert got == [TX_STATUS_PENDING] * 4
        s = app.ingest.stats()
        assert s["queued"] == 0
        assert s["flushes"] == 1 and s["admitted"] == 4
        assert s["batch_size_mean"] == 4.0
        assert s["occupancy_mean"] == 1.0
    finally:
        app.graceful_stop()


def test_flush_on_deadline(clock):
    """A lone submission flushes when the VirtualTimer deadline fires on
    the crank — no tx waits longer than INGEST_BATCH_DEADLINE_MS."""
    app = make_app(clock, 61, INGEST_BATCH_DEADLINE_MS=50)
    try:
        seq = _root_seq(app)
        got = []
        assert (
            app.ingest.submit(_payment(app, 0, seq + 1), on_status=got.append)
            is None
        )
        assert got == []
        clock.crank_for(0.2)
        assert got == [TX_STATUS_PENDING]
        assert app.ingest.stats()["flushes"] == 1
    finally:
        app.graceful_stop()


def test_shutdown_drains_then_passes_through(clock):
    """Shutdown drains the accumulator (every queued submitter gets an
    answer) and late arrivals fall through to the herder per-tx."""
    app = make_app(clock, 62, INGEST_BATCH_DEADLINE_MS=60_000)
    try:
        seq = _root_seq(app)
        got = []
        assert (
            app.ingest.submit(_payment(app, 0, seq + 1), on_status=got.append)
            is None
        )
        app.ingest.shutdown()
        assert got == [TX_STATUS_PENDING]
        assert app.ingest.submit(_payment(app, 1, seq + 2)) == TX_STATUS_PENDING
    finally:
        app.graceful_stop()


# -- verdict latch / edge shed ----------------------------------------------


def test_verdict_latch_and_edge_shed(clock):
    """One ingest flush (a) latches every VALID triple so the herder's
    eager check_signature is an all-hit by construction, (b) sheds the
    all-invalid tx at the edge with txBAD_AUTH while latching NOTHING
    (the valid-only quarantine contract), and (c) passes the triple-less
    unknown-account tx through — the herder stays the validity oracle."""
    verify_cache().clear()
    app = make_app(clock, 63)
    try:
        seq = _root_seq(app)
        good = _payment(app, "latch-good", seq + 1)
        bad = _payment(app, "latch-bad", seq + 2, corrupt=True)
        stranger = SecretKey.pseudo_random_for_testing(777)
        unknown = T.tx_from_ops(
            app, stranger, 1, [T.payment_op(T.get_account("x"), 1)], fee=100
        )

        cache = verify_cache()
        k_good = [
            cache.key_for(pk, sig, msg)
            for pk, msg, sig in good.candidate_signature_pairs(app.database)
        ]
        k_bad = [
            cache.key_for(pk, sig, msg)
            for pk, msg, sig in bad.candidate_signature_pairs(app.database)
        ]
        assert k_good and k_bad
        assert unknown.candidate_signature_pairs(app.database) == []

        PubKeyUtils.flush_verify_sig_cache_counts()
        assert app.ingest.submit_sync(good) == TX_STATUS_PENDING
        # the eager per-sig check inside recv_transaction ran AFTER the
        # batch latch: all-hit, zero misses
        hits, misses = PubKeyUtils.flush_verify_sig_cache_counts()
        assert hits >= 1 and misses == 0
        assert cache.peek_many(k_good) == [True] * len(k_good)

        assert app.ingest.submit_sync(bad) == TX_STATUS_ERROR
        assert bad.get_result_code() == X.TransactionResultCode.txBAD_AUTH
        assert cache.peek_many(k_bad) == [None] * len(k_bad)
        assert app.ingest.stats()["rejects"]["badsig"] == 1

        assert app.ingest.submit_sync(unknown) == TX_STATUS_ERROR
        assert app.ingest.stats()["passthrough"] == 1

        # resubmission: DUPLICATE at the herder, and the flush's peek is
        # a pure cache hit — no triple re-verified
        v0 = app.ingest.stats()["verify"]
        assert app.ingest.submit_sync(good) == TX_STATUS_DUPLICATE
        v1 = app.ingest.stats()["verify"]
        assert v1["cache_hits"] == v0["cache_hits"] + len(k_good)
        assert v1["triples_verified"] == v0["triples_verified"]
    finally:
        app.graceful_stop()


def test_wedge_latch_isolation_caller_ingest():
    """The TpuSigBackend wedge latch is scoped per caller class (ISSUE
    r10): a stalled CALLER_INGEST micro-batch latches only the ingest
    plane onto host — the synchronous close path still probes (and owns)
    the device independently."""
    import threading

    from stellar_tpu.crypto.sigbackend import (
        CALLER_CLOSE,
        CALLER_INGEST,
        TpuSigBackend,
    )

    be = TpuSigBackend.__new__(TpuSigBackend)  # skip JAX verifier init
    be.cpu_cutover = 0
    be.n_cutover_items = 0
    be.n_wedge_fallback_items = 0
    be._wedged_until = {}
    be.n_latch_flips = {}
    be._wedge_lock = threading.Lock()
    be.DEVICE_TIMEOUT = 0.2

    class WedgedVerifier:
        calls = 0

        def cold_buckets(self, n, host_assist=True):
            return 0  # past warm-up: the short DEVICE_TIMEOUT applies

        def verify(self, items):
            WedgedVerifier.calls += 1
            threading.Event().wait()  # wedged forever

    be._verifier = WedgedVerifier()
    sk = SecretKey.pseudo_random_for_testing(5)
    msg = b"ingest-wedge"
    items = [(sk.public_raw, msg, sk.sign(msg))]
    # a stalled ingest flush latches the INGEST class...
    assert be.verify_batch(items, caller=CALLER_INGEST) == [True]
    assert be.n_latch_flips == {CALLER_INGEST: 1}
    # ...latched: the next ingest flush goes straight to host
    assert be.verify_batch(items, caller=CALLER_INGEST) == [True]
    assert WedgedVerifier.calls == 1
    assert be.n_wedge_fallback_items == 2
    # ...while the close path still probes the device for itself
    assert be.verify_batch(items, caller=CALLER_CLOSE) == [True]
    assert WedgedVerifier.calls == 2
    assert be.n_latch_flips == {CALLER_INGEST: 1, CALLER_CLOSE: 1}


# -- admission control ------------------------------------------------------


def test_rate_limit_token_bucket(clock):
    """Per-account token bucket on the VirtualClock: the burst admits,
    the next tx from the same account answers TRY_AGAIN_LATER, other
    accounts have their own buckets, and tokens refill with time."""
    app = make_app(
        clock, 64,
        INGEST_RATE_LIMIT=1, INGEST_RATE_BURST=2,
        INGEST_BATCH_MAX=64, INGEST_BATCH_DEADLINE_MS=60_000,
    )
    try:
        seq = _root_seq(app)
        assert app.ingest.submit(_payment(app, "rl-0", seq + 1)) is None
        assert app.ingest.submit(_payment(app, "rl-1", seq + 2)) is None
        got = []
        st = app.ingest.submit(
            _payment(app, "rl-2", seq + 3), on_status=got.append
        )
        assert st == INGEST_STATUS_TRY_AGAIN
        assert got == [INGEST_STATUS_TRY_AGAIN]
        assert app.ingest.stats()["rejects"]["ratelimit"] == 1
        # a different source account has its own bucket
        alice = T.get_account("ing-rl-alice")
        other = T.tx_from_ops(
            app, alice, 1, [T.payment_op(T.get_account("x"), 1)], fee=100
        )
        assert app.ingest.submit(other) is None
        # refill at 1 token/sec on the virtual clock
        clock.crank_for(1.1)
        assert app.ingest.submit(_payment(app, "rl-3", seq + 4)) is None
        assert app.ingest.stats()["rate_limit"]["tracked_accounts"] == 2
    finally:
        app.graceful_stop()


def test_surge_eviction_fee_ordering(clock):
    """Fee-based surge admission at the front door — the close path's
    surge_pricing_filter ordering generalized to the accumulator: at the
    high water a higher-fee tx takes the lowest-fee seat (the evictee is
    answered TRY_AGAIN_LATER), and a lower-fee tx than every seat is
    turned away at the door."""
    app = make_app(
        clock, 65,
        INGEST_SURGE_HIGH_WATER=2,
        INGEST_BATCH_MAX=64, INGEST_BATCH_DEADLINE_MS=60_000,
    )
    try:
        seq = _root_seq(app)
        low_cb, mid_cb = [], []
        st = app.ingest.submit(
            _payment(app, "sg-0", seq + 1, fee=100), on_status=low_cb.append
        )
        assert st is None
        st = app.ingest.submit(
            _payment(app, "sg-1", seq + 2, fee=500), on_status=mid_cb.append
        )
        assert st is None
        # at the high water: fee 1000 evicts the fee-100 seat
        assert app.ingest.submit(_payment(app, "sg-2", seq + 3, fee=1000)) is None
        assert low_cb == [INGEST_STATUS_TRY_AGAIN]
        assert mid_cb == []
        assert app.ingest.stats()["rejects"]["surge"] == 1
        # fee 100 is below every remaining seat: rejected at the door
        got = []
        st = app.ingest.submit(
            _payment(app, "sg-3", seq + 4, fee=100), on_status=got.append
        )
        assert st == INGEST_STATUS_TRY_AGAIN
        assert got == [INGEST_STATUS_TRY_AGAIN]
        assert app.ingest.stats()["rejects"]["surge"] == 2
        assert app.ingest.stats()["queued"] == 2
    finally:
        app.graceful_stop()


def test_replay_edge_skips_admission(clock):
    """Catchup/downloaded-txset replay rides the batched verify but NO
    rate/surge admission — a replayed externalized set must never be
    admission-wedged."""
    app = make_app(clock, 66, INGEST_RATE_LIMIT=1, INGEST_RATE_BURST=1)
    try:
        seq = _root_seq(app)
        txs = [_payment(app, "rp-%d" % i, seq + 1 + i) for i in range(4)]
        assert app.ingest.submit_replay(txs) == [TX_STATUS_PENDING] * 4
        assert app.ingest.stats()["rejects"]["ratelimit"] == 0
    finally:
        app.graceful_stop()


# -- admission measured from inside (PR 51) ---------------------------------


def _phase_sum(s) -> float:
    return sum(s["phase_s"].values())


@pytest.mark.parametrize("traced", [True, False], ids=["traced", "untraced"])
def test_phase_counters_over_a_mixed_run(clock, traced):
    """``flushed`` is the sum of the batch sizes whatever edge filled the
    batch, a phase's seconds are inside the edge that ran it (every flush
    of the synchronous stretch runs inside ``submit_sync``, so the four
    phases fit in ``submit_s`` there; ``submit`` and ``submit_replay`` move
    the phases and not ``submit_s``), a shed entry never reaches the
    herder's phase — and all of it counts with the tracer off."""
    app = make_app(
        clock, 160 + traced, INGEST_BATCH_MAX=4, INGEST_BATCH_DEADLINE_MS=60_000,
        TRACE_ENABLED=traced,
    )
    try:
        plane = app.ingest
        seq = _root_seq(app)
        PubKeyUtils.clear_verify_sig_cache()  # the other case's verdicts
        s0 = plane.stats()
        assert s0["flushed"] == 0 and _phase_sum(s0) == 0.0

        # the synchronous edge: six flushes of one, the last of them shed
        for i in range(5):
            assert plane.submit_sync(_payment(app, "ph-%d" % i, seq + 1 + i)) == TX_STATUS_PENDING
        s1 = plane.stats()
        assert s1["flushed"] == 5 and s1["flushes"] == 5
        assert all(v > 0.0 for v in s1["phase_s"].values()), s1["phase_s"]
        assert _phase_sum(s1) <= s1["submit_s"]
        assert plane.submit_sync(_payment(app, "ph-bad", seq + 6, corrupt=True)) == TX_STATUS_ERROR
        s2 = plane.stats()
        assert s2["flushed"] == 6 and s2["rejects"]["badsig"] == 1
        assert s2["phase_s"]["herder"] == s1["phase_s"]["herder"]
        assert s2["phase_s"]["collect"] > s1["phase_s"]["collect"]
        assert s2["phase_s"]["verify"] > s1["phase_s"]["verify"]
        assert _phase_sum(s2) <= s2["submit_s"]

        # the overlay edge: a size-triggered batch of four, then three that
        # a synchronous submission takes with it
        for i in range(4):
            plane.submit(_payment(app, "ph-o%d" % i, seq + 6 + i))
        for i in range(3):
            plane.submit(_payment(app, "ph-p%d" % i, seq + 10 + i))
        assert plane.submit_sync(_payment(app, "ph-q", seq + 13)) == TX_STATUS_PENDING
        # the replay edge: four and two
        assert plane.submit_replay(
            [_payment(app, "ph-r%d" % i, seq + 14 + i) for i in range(6)]
        ) == [TX_STATUS_PENDING] * 6
        s3 = plane.stats()
        sizes = [1] * 6 + [4, 4, 4, 2]
        assert s3["flushes"] == len(sizes) and s3["flushed"] == sum(sizes)
        assert s3["batch_size_mean"] * s3["flushes"] == pytest.approx(s3["flushed"])
        assert s3["occupancy_mean"] == pytest.approx(s3["batch_size_mean"] / 4)
        assert s3["admitted"] == s3["flushed"] - 1
        assert all(s3["phase_s"][k] > s2["phase_s"][k] for k in s3["phase_s"]), s3["phase_s"]
        assert s3["submitted"] == 7
        # no key of the block went away
        assert set(s0) == set(s3)
        spans = app.tracer.spans()
        if traced:
            assert [s.name for s in spans].count("ingest.flush") == len(sizes)
        else:
            assert spans == []
    finally:
        app.graceful_stop()


@pytest.mark.parametrize("edge", ["deadline", "sync", "sync-takes-queued", "replay"])
def test_queue_wait_is_read_on_the_virtual_clock(clock, edge):
    """An entry left to the deadline waited the deadline, exactly; one the
    synchronous edge flushed at once, or the replay edge, waited 0; queued
    entries a synchronous submission takes with it waited since their own
    arrival."""
    app = make_app(clock, 164, INGEST_BATCH_DEADLINE_MS=50)
    try:
        plane = app.ingest
        seq = _root_seq(app)
        clock.crank_for(1.0)  # away from 0: a wait is a difference
        at = clock.now()
        if edge == "deadline":
            assert plane.submit(_payment(app, "qw-0", seq + 1)) is None
            clock.crank_for(0.2)
            want = (at + plane.deadline_s) - at
            assert want == pytest.approx(0.05)
        elif edge == "sync":
            assert plane.submit_sync(_payment(app, "qw-0", seq + 1)) == TX_STATUS_PENDING
            want = 0.0
        elif edge == "sync-takes-queued":
            assert plane.submit(_payment(app, "qw-0", seq + 1)) is None
            clock.set_current_virtual_time(at + 0.02)
            assert plane.submit_sync(_payment(app, "qw-1", seq + 2)) == TX_STATUS_PENDING
            want = clock.now() - at
            assert 0.0 < want < plane.deadline_s
        else:
            assert plane.submit_replay([_payment(app, "qw-0", seq + 1)]) == [TX_STATUS_PENDING]
            want = 0.0
        s = plane.stats()
        assert s["queued"] == 0 and s["flushes"] == 1
        assert s["queue_wait_s"] == want and s["queue_wait_max_s"] == want
    finally:
        app.graceful_stop()


# -- differential -----------------------------------------------------------


def test_ledger_differential_ingest_on_off(clock):
    """The transparency contract: INGEST_BATCH on vs off yield the same
    submission statuses, bit-identical ledger hashes, and bit-identical
    SQL state for a mixed stream (valid / invalid-sig / unknown-account)
    across two consensus closes."""
    apps = [
        make_app(clock, 67 + i, INGEST_BATCH=on)
        for i, on in enumerate((True, False))
    ]
    try:
        assert apps[0].ingest.enabled and not apps[1].ingest.enabled
        for rnd in range(2):
            per_app = []
            for app in apps:
                seq = _root_seq(app)
                stranger = SecretKey.pseudo_random_for_testing(888 + rnd)
                txs = (
                    _payment(app, "df-%d-0" % rnd, seq + 1),
                    _payment(app, "df-%d-1" % rnd, seq + 2),
                    _payment(app, "df-%d-2" % rnd, seq + 3, corrupt=True),
                    T.tx_from_ops(
                        app, stranger, 1,
                        [T.payment_op(T.get_account("x"), 1)], fee=100,
                    ),
                )
                per_app.append([app.ingest.submit_sync(tx) for tx in txs])
            assert per_app[0] == per_app[1], "submission statuses diverged"
            assert per_app[0][:2] == [TX_STATUS_PENDING] * 2
            assert per_app[0][2:] == [TX_STATUS_ERROR] * 2
            targets = []
            for app in apps:
                lm = app.ledger_manager
                targets.append(lm.get_last_closed_ledger_num() + 1)
                app.herder.trigger_next_ledger(lm.get_ledger_num())
            assert clock.crank_until(
                lambda: all(
                    a.ledger_manager.get_last_closed_ledger_num() >= t
                    for a, t in zip(apps, targets)
                ),
                30,
            )
            assert (
                apps[0].ledger_manager.last_closed.hash
                == apps[1].ledger_manager.last_closed.hash
            ), "ledger hash diverged at round %d" % rnd
        assert T.dump_state(apps[0].database) == T.dump_state(
            apps[1].database
        ), "SQL state diverged"
    finally:
        for app in apps:
            app.graceful_stop()
