"""CATCHUP_COMPLETE's replay (``history/catchupsm.py``): one ledger a clock
post, through the close pipeline with the signatures of the ledgers ahead
prefetched in batches filled across ledger boundaries
(``ledger/closepipeline.py``), held to the publisher's hashes, to
``CLOSE_PIPELINE = False`` and to a plain replay of the archive's files
(``reference_apply.replay_archive``) that shares nothing with the program.

One seeded archive a module: a cpu-backend publisher closes one checkpoint
of ``FREQ - 1`` ledgers — ledger 2 creates ``ACCOUNTS`` accounts, every later
one carries ``WIDTH`` single-signature native payments between distinct
accounts — and publishes it to a file archive (get / put = ``cp``).  Every
test has a time limit of its own (``limit``)."""

import functools
import os
import random
import shutil
import signal
import struct

import pytest

import reference_apply as plain
from stellar_tpu.crypto.keys import PubKeyUtils, verify_cache
from stellar_tpu.history import catchupsm
from stellar_tpu.ledger.closepipeline import ClosePipeline
from stellar_tpu.ledger.manager import LedgerState
from stellar_tpu.main.application import Application
from stellar_tpu.tx import testutils as T
from stellar_tpu.util.clock import REAL_TIME, VirtualClock

FREQ = 16
ANCHOR = FREQ - 1
WIDTH = 12
ACCOUNTS = 2 * WIDTH
SEED = 39
BALANCE = 10**10


def limit(seconds):
    """The test fails, where it would hang, after ``seconds``."""

    def wrap(fn):
        @functools.wraps(fn)
        def run(*a, **kw):
            def late(*_):
                raise TimeoutError(f"{fn.__name__} ran past its {seconds} s")

            old = signal.signal(signal.SIGALRM, late)
            signal.alarm(seconds)
            try:
                return fn(*a, **kw)
            finally:
                signal.alarm(0)
                signal.signal(signal.SIGALRM, old)

        return run

    return wrap


def archive_spec(archive_dir, writable=False):
    spec = {"get": f"cp {archive_dir}/{{0}} {{1}}"}
    if writable:
        spec["put"] = f"cp {{0}} {archive_dir}/{{1}}"
        spec["mkdir"] = f"mkdir -p {archive_dir}/{{0}}"
    return {"test": spec}


def make_app(clock, instance, archive_dir, writable=False, **settings):
    cfg = T.get_test_config(instance, backend=settings.pop("backend", "cpu"))
    cfg.CHECKPOINT_FREQUENCY = FREQ
    cfg.HISTORY = archive_spec(archive_dir, writable)
    cfg.CATCHUP_COMPLETE = True
    cfg.HTTP_PORT = 0
    for k, v in settings.items():
        assert hasattr(cfg, k), k
        setattr(cfg, k, v)
    shutil.rmtree(cfg.BUCKET_DIR_PATH, ignore_errors=True)
    app = Application.create(clock, cfg, new_db=True)
    app.start()
    return app


class Archive:
    """What the publisher left, and what it knows of it."""

    def __init__(self, directory):
        self.dir = directory
        self.hashes = {}  # ledger -> hash
        self.keys = [T.get_account(1000 * SEED + i) for i in range(ACCOUNTS)]
        self.payments = 0


@pytest.fixture(scope="module")
def archive(tmp_path_factory):
    directory = str(tmp_path_factory.mktemp("catchup-archive"))
    out = Archive(directory)
    clock = VirtualClock(REAL_TIME)
    app = make_app(clock, 150, directory, writable=True)
    try:
        lm = app.ledger_manager
        out.passphrase = app.config.NETWORK_PASSPHRASE
        out.hashes[1] = lm.last_closed.hash
        root = T.root_key_for(app)
        rng = random.Random(SEED)
        next_seq = {}

        def close(txs):
            T.close_ledger_on(app, lm.last_closed.header.scpValue.closeTime + 5, txs)
            out.hashes[lm.last_closed.header.ledgerSeq] = lm.last_closed.hash

        close([T.tx_from_ops(app, root, 1, [T.create_account_op(k, BALANCE) for k in out.keys])])
        while lm.get_last_closed_ledger_num() < ANCHOR:
            order = list(range(ACCOUNTS))
            rng.shuffle(order)
            txs = []
            for s, d in zip(order[:WIDTH], order[WIDTH:]):
                seq = next_seq.get(s, (2 << 32) + 1)
                next_seq[s] = seq + 1
                txs.append(T.tx_from_ops(app, out.keys[s], seq, [T.payment_op(out.keys[d], 1000 + s)]))
            close(txs)
            out.payments += len(txs)
        assert clock.crank_until(lambda: app.history_manager.get_publish_success_count() > 0, 60)
        out.fee_pool = lm.last_closed.header.feePool
        out.bucket_list_hash = lm.last_closed.header.bucketListHash
    finally:
        app.graceful_stop()
        clock.shutdown()
    return out


@pytest.fixture
def clock():
    c = VirtualClock(REAL_TIME)
    yield c
    c.shutdown()


@pytest.fixture(autouse=True)
def cold_cache():
    # the verify cache is the process's: what the publisher (or the test
    # before) latched must not answer for a replay
    PubKeyUtils.clear_verify_sig_cache()


def catch_up(app, clock, seconds=120):
    """-> {ledger: hash} of every ledger the catch-up closed."""
    lm = app.ledger_manager
    closed = {}
    inner = lm.close_ledger

    def close_ledger(ledger_data):
        inner(ledger_data)
        closed[lm.last_closed.header.ledgerSeq] = lm.last_closed.hash

    lm.close_ledger = close_ledger
    lm.start_catchup()
    assert clock.crank_until(
        lambda: lm.state != LedgerState.LM_CATCHING_UP_STATE, seconds
    ), "the catch-up did not end"
    return closed


def flush_sizes(app):
    """Record the size of every batch the inner backend is handed."""
    sizes = []
    inner = app.sig_backend.inner
    verify = inner.verify_batch

    def verify_batch(items, caller="close"):
        sizes.append(len(items))
        return verify(items, caller=caller)

    inner.verify_batch = verify_batch
    return sizes


# -- the replay ------------------------------------------------------------------


@limit(180)
@pytest.mark.parametrize("pipeline", [True, False])
def test_replay_gives_the_publishers_hashes_ledger_for_ledger(archive, clock, pipeline):
    app = make_app(clock, 151 + pipeline, archive.dir, CLOSE_PIPELINE=pipeline, PARANOID_MODE=True)
    try:
        closed = catch_up(app, clock)
        assert app.ledger_manager.state == LedgerState.LM_SYNCED_STATE
        assert closed == {seq: h for seq, h in archive.hashes.items() if seq > 1}
        assert app.bucket_manager.get_hash() == archive.bucket_list_hash
        assert app.invariants.total_violations == 0
        assert app.invariants.closes_checked == ANCHOR - 1
        pipe = app.close_pipeline
        if pipeline:
            # every payment ledger but the first (its accounts did not exist
            # when the ledger before it looked) joined a prefetch
            assert pipe.n_joined >= ANCHOR - 3 and pipe.n_fallback == 0
            assert app.history_manager.stats()["triples_prefetched"] == pipe.n_items > 0
        else:
            assert pipe.n_dispatched == pipe.n_joined == 0
        assert not pipe._futures and not pipe._carry and pipe._ahead == 0
    finally:
        app.graceful_stop()


@limit(180)
def test_system_equals_the_plain_archive_replay(archive, clock):
    ref = plain.replay_archive(archive.dir, ANCHOR, archive.passphrase)
    assert {k: ref[k] for k in ("headers_off", "sets_off", "signatures_bad", "results_off", "fee_pools_off")} == {
        "headers_off": 0, "sets_off": 0, "signatures_bad": 0, "results_off": 0, "fee_pools_off": 0,
    }
    assert ref["txs"] == ref["signatures"] == archive.payments + 1
    assert ref["hashes"] == archive.hashes  # the header chain, ledger for ledger
    app = make_app(clock, 153, archive.dir)
    try:
        closed = catch_up(app, clock)
        lcl = app.ledger_manager.last_closed
        assert closed[ANCHOR] == lcl.hash == ref["hashes"][ANCHOR]
        assert lcl.header.bucketListHash == ref["bucket_list_hash"]
        assert lcl.header.feePool == ref["fee_pool"] == archive.fee_pool
        stored = {
            PubKeyUtils.from_strkey(aid).value: (balance, seq)
            for aid, balance, seq in app.database.query_all("SELECT accountid, balance, seqnum FROM accounts")
        }
        assert stored == ref["accounts"] and len(stored) == ACCOUNTS + 1
    finally:
        app.graceful_stop()


def forge(archive, directory):
    """A copy of the archive with a forged signature planted in a payment of
    ledger 9 and the chain made consistent around it (the benchmark's own
    forger) -> (the ledger, the forged (key, message, signature))."""
    import hashlib

    from benchmarks.generators.replay import forge_archive

    shutil.rmtree(directory, ignore_errors=True)
    shutil.copytree(archive.dir, directory)
    network_id = hashlib.sha256(archive.passphrase.encode()).digest()
    return 9, forge_archive(directory, ANCHOR, network_id, 9, 3)


@limit(180)
def test_forged_signature_fails_the_catchup_and_latches_nothing(archive, clock, tmp_path, monkeypatch):
    monkeypatch.setattr(catchupsm, "MAX_RETRIES", 1)
    monkeypatch.setattr(catchupsm, "RETRY_DELAY_SECONDS", 0.01)
    seq, (key, msg, sig) = forge(archive, str(tmp_path / "forged"))
    ref = plain.replay_archive(str(tmp_path / "forged"), ANCHOR, archive.passphrase)
    # the forger left a chain that verifies and sets that hash to their headers
    assert (ref["headers_off"], ref["sets_off"], ref["signatures_bad"]) == (0, 0, 1)
    app = make_app(clock, 154, str(tmp_path / "forged"))
    try:
        errors = []
        monkeypatch.setattr(catchupsm.log, "error", lambda fmt, *a: errors.append(fmt % a))
        closed = catch_up(app, clock)
        lm = app.ledger_manager
        assert lm.state == LedgerState.LM_BOOTING_STATE
        assert app.history_manager.catchup.state == "FAILED"
        # the ledgers before the forged one applied with the archive's
        # hashes; the forged one closed with its transaction refused, to a
        # hash that is not the forger's, and the replay stopped there
        assert lm.get_last_closed_ledger_num() == seq and sorted(closed) == list(range(2, seq + 1))
        assert all(closed[s] == archive.hashes[s] for s in range(2, seq))
        assert any(f"replayed ledger {seq} hash mismatch" in e for e in errors)
        # the forged triple has no verdict, nothing is in flight, and what
        # the prefetch had latched for the ledgers ahead is withdrawn
        cache = verify_cache()
        assert cache.peek_many([cache.key_for(key, sig, msg)]) == [None]
        pipe = app.close_pipeline
        assert not pipe._futures and not pipe._carry and not pipe._candidates and pipe._ahead == 0
        assert pipe.n_quarantined >= 1
        ahead = [
            cache.key_for(e["source"], e["signatures"][0][1], e["hash"])
            for s, _p, envs in (
                plain.tx_entry(b, app.network_id)
                for b in plain.records(plain.archive_file(archive.dir, "transactions", ANCHOR))
            )
            if s > seq
            for e in envs
        ]
        assert ahead and cache.peek_many(ahead) == [None] * len(ahead)
    finally:
        app.graceful_stop()


@limit(180)
def test_prefetch_never_evicts_a_verdict_before_its_ledger_used_it(archive, clock, monkeypatch):
    """A range larger than the cache: the horizon keeps the prefetch from
    pushing out what the closes have not used yet."""
    cache = verify_cache()
    monkeypatch.setattr(cache, "capacity", 64)
    assert archive.payments > 2 * cache.capacity
    app = make_app(clock, 155, archive.dir, SIG_BATCH_MAX=8)
    try:
        sizes = flush_sizes(app)
        pipe = app.close_pipeline
        assert pipe._horizon(app.sig_backend) == 64 // 2 - 8
        ahead = []
        dispatch = pipe.dispatch_ahead
        monkeypatch.setattr(pipe, "dispatch_ahead", lambda tr: (dispatch(tr), ahead.append(pipe._ahead)))
        eager = cache.eager_host_verifies
        closed = catch_up(app, clock)
        assert closed[ANCHOR] == archive.hashes[ANCHOR]
        # not one signature check at apply missed the cache
        assert cache.eager_host_verifies == eager
        # the horizon held the prefetch back (a set is collected whole) ...
        assert max(ahead) <= 24 and len(cache) <= 64
        # ... and what left the carry were whole batches, but where the
        # next ledger's own triples waited in it
        assert pipe.n_flushes >= 8 and sum(1 for n in sizes if n % 8 == 0) >= 6
    finally:
        app.graceful_stop()


@limit(180)
def test_the_clocks_other_work_runs_between_replayed_ledgers(archive, clock):
    app = make_app(clock, 156, archive.dir)
    try:
        lm = app.ledger_manager
        seen = []

        def tick():
            info = app.command_handler.handle_info({})["info"]
            seen.append((info["state"], info["ledger"]["num"], info["history"]["catchup"]))
            if lm.state == LedgerState.LM_CATCHING_UP_STATE:
                clock.post(tick)

        clock.post(tick)
        catch_up(app, clock)
        replaying = [(num, c["ledgers_left"]) for state, num, c in seen if c and c["state"] == "APPLYING"]
        # the route answered after every single replayed ledger
        assert {num for num, _ in replaying} >= set(range(2, ANCHOR))
        assert all(left == ANCHOR - num for num, left in replaying)
        assert all(state == "Catching up" for state, _n, c in seen if c and c["state"] == "APPLYING")
    finally:
        app.graceful_stop()


@limit(180)
@pytest.mark.parametrize("forged", [False, True])
def test_the_decoded_range_is_parked_out_of_the_full_passes_for_the_replay_alone(
    archive, clock, tmp_path, monkeypatch, forged
):
    """While the range replays, what the decode made is out of the full
    collector passes' sight (``collector.park``); a round that ends, by its
    finish or by its failure, gives it all back."""
    import gc

    directory = archive.dir
    if forged:
        monkeypatch.setattr(catchupsm, "MAX_RETRIES", 0)
        directory = str(tmp_path / "forged")
        forge(archive, directory)
    gc.unfreeze()
    app = make_app(clock, 160 + forged, directory)
    try:
        parked = []

        def tick():
            fsm = app.history_manager.catchup
            if fsm is not None and fsm.state == "APPLYING":
                parked.append(gc.get_freeze_count())
            if app.ledger_manager.state == LedgerState.LM_CATCHING_UP_STATE:
                clock.post(tick)

        clock.post(tick)
        catch_up(app, clock)
        assert app.history_manager.catchup.state == ("FAILED" if forged else "END")
        # at least the frames of the range: a payment is tens of objects
        assert parked and min(parked) > archive.payments
        assert gc.get_freeze_count() == 0
    finally:
        app.graceful_stop()


@limit(180)
def test_spans_and_counters_are_there_and_nest_as_stated(archive, clock):
    app = make_app(clock, 157, archive.dir)
    try:
        app.tracer.clear()
        catch_up(app, clock)
        spans = app.tracer.spans()
        by = {}
        for s in spans:
            by.setdefault(s.name, []).append(s)
        (rnd,) = by["catchup.round"]
        assert rnd.attrs["mode"] == "complete" and rnd.attrs["ok"] is True
        assert (rnd.attrs["first"], rnd.attrs["last"]) == (2, ANCHOR)
        for name in ("catchup.fetch", "catchup.decode", "catchup.verify_chain", "catchup.prefetch"):
            (s,) = by[name]
            assert s.parent == rnd.sid and rnd.start <= s.start and s.end <= rnd.end, name
        assert by["catchup.fetch"][0].attrs["files"] == 2 and by["catchup.fetch"][0].attrs["bytes"] > 0
        assert by["catchup.decode"][0].attrs["headers"] == ANCHOR
        assert by["catchup.decode"][0].attrs["txs"] == archive.payments + 1
        pre = by["catchup.prefetch"][0].attrs
        assert pre["sets"] == ANCHOR - 1 and pre["signatures"] == archive.payments + 1
        applies = by["catchup.apply_ledger"]
        assert [s.attrs["seq"] for s in applies] == list(range(2, ANCHOR + 1))
        assert sum(s.attrs["txs"] for s in applies) == archive.payments + 1
        closes = {s.parent: s for s in by["ledger.close"]}
        for s in applies:
            assert s.parent == rnd.sid and s.req == s.attrs["seq"]
            assert closes[s.sid].attrs["seq"] == s.attrs["seq"]
        close_sids = {s.sid for s in by["ledger.close"]}
        phase_sids = {s.sid for s in by["close.sig_flush"]}
        assert all(s.parent in phase_sids for s in by["close.pipeline.join"])
        assert all(s.parent in close_sids for s in by["close.sig_flush"])
        history = app.command_handler.handle_info({})["info"]["history"]
        assert history["rounds"] == 1 and history["ledgers_replayed"] == ANCHOR - 1
        assert history["txs_replayed"] == archive.payments + 1
        assert history["triples_prefetched"] == app.close_pipeline.stats()["prefetched_items"] > 0
        assert history["catchup"]["state"] == "END" and history["catchup"]["ledgers_left"] == 0
    finally:
        app.graceful_stop()


@limit(600)
def test_two_catchups_in_one_process_compile_and_load_nothing_the_second_time(archive, clock):
    """The verify programs are the process's: the second fresh node's first
    dispatch of a bucket finds kernel, program and executable there."""
    from jax import monitoring

    events = []
    monitoring.register_event_duration_secs_listener(
        lambda event, seconds, **_kw: events.append(event) if "/jax/core/compile" in event else None
    )
    stats = []
    for instance in (158, 159):
        PubKeyUtils.clear_verify_sig_cache()
        mark = len(events)
        app = make_app(clock, instance, archive.dir, backend="tpu", SIG_BATCH_MAX=16, TPU_CPU_CUTOVER=0)
        try:
            closed = catch_up(app, clock, seconds=500)
            assert closed[ANCHOR] == archive.hashes[ANCHOR]
            sb = app.sig_backend.stats()
            stats.append((sb, len(events) - mark))
        finally:
            app.graceful_stop()
    (first, _), (second, compiled) = stats
    assert first["device_calls"] > 0 and second["device_calls"] == first["device_calls"]
    assert compiled == 0, "the second node traced, lowered or compiled"
    # its books name the dispatch that paid, the first node's
    assert second["first_dispatch"]["buckets"] == first["first_dispatch"]["buckets"]
    assert second["first_dispatch"]["recompiles"]["events"] == 0


# -- the pipeline's coalescing, alone ----------------------------------------------


class _Tx:
    def __init__(self, n, missing=False):
        self.n, self.missing = n, missing
        self.envelope = type("E", (), {"signatures": [None]})()

    def get_full_hash(self):
        return struct.pack(">I", self.n) * 8

    def candidate_signature_pairs(self, db, tally=None):
        if self.missing:
            tally["missing"] += 1
            return []
        return [(b"k%d" % self.n, b"m", b"s")]


class _Backend:
    cache = type("C", (), {"capacity": 64})()

    def __init__(self):
        self.flushes = []

    def verify_batch_async(self, items, caller=None):
        from stellar_tpu.crypto.sigbackend import SigFlushFuture

        self.flushes.append([pk for pk, _m, _s in items])
        fut = SigFlushFuture(len(items))
        fut._complete(result=[True] * len(items))
        return fut


def _pipeline(batch=8):
    from stellar_tpu.trace import NULL_TRACER

    app = type("A", (), {})()
    app.config = type("Cfg", (), {"SIG_BATCH_MAX": batch})()
    app.sig_backend = _Backend()
    app.database = None
    return ClosePipeline(app), app.sig_backend, NULL_TRACER


def _set(first, n, missing=False):
    return type("S", (), {"transactions": [_Tx(first + i, missing) for i in range(n)]})()


@limit(30)
def test_one_upcoming_set_leaves_as_the_one_flush_of_before():
    pipe, backend, tracer = _pipeline()
    s = _set(0, 5)
    pipe.note_upcoming(s.transactions)
    pipe.dispatch_ahead(tracer)
    assert [len(f) for f in backend.flushes] == [5] and pipe.n_dispatched == 1
    assert pipe.join_prewarm(s, tracer) and pipe._ahead == 0 and not pipe._futures


@limit(30)
def test_upcoming_sets_coalesce_into_whole_batches_across_ledger_boundaries():
    pipe, backend, tracer = _pipeline(batch=8)
    sets = [_set(10 * i, 5) for i in range(9)]
    for s in sets:
        pipe.note_upcoming(s.transactions)
    # horizon 64 // 2 - 8 = 24: four sets are 20 triples, a fifth would pass it
    pipe.dispatch_ahead(tracer)
    assert pipe._ahead == 20 and len(pipe._candidates) == 5
    # the next set to close has triples in the carry: all of it leaves
    assert [len(f) for f in backend.flushes] == [20]
    for i, s in enumerate(sets):
        assert pipe.join_prewarm(s, tracer), i
        pipe.dispatch_ahead(tracer)
    # from then on whole batches of 8, cut inside a set, and the tail when
    # the set it belongs to closes next
    assert [len(f) for f in backend.flushes] == [20, 8, 8, 8, 1]
    assert sum(backend.flushes, []) == [b"k%d" % (10 * i + j) for i in range(9) for j in range(5)]
    assert pipe.n_dispatched == pipe.n_joined == 9 and pipe.n_items == 45
    assert pipe._ahead == 0 and not pipe._carry and not pipe._futures


@limit(30)
def test_a_set_whose_accounts_are_not_there_yet_waits_uncollected():
    pipe, backend, tracer = _pipeline()
    a, b, c = _set(0, 3), _set(10, 3, missing=True), _set(20, 3)
    for s in (a, b, c):
        pipe.note_upcoming(s.transactions)
    pipe.dispatch_ahead(tracer)
    assert [len(f) for f in backend.flushes] == [3] and len(pipe._candidates) == 2
    assert pipe.join_prewarm(a, tracer)
    for tx in b.transactions:
        tx.missing = False  # the ledger between created them
    pipe.dispatch_ahead(tracer)
    assert [len(f) for f in backend.flushes] == [3, 6] and not pipe._candidates
    # one that closes before its accounts are there is its own close's to flush
    late = _set(30, 3, missing=True)
    pipe.note_upcoming(late.transactions)
    assert pipe.join_prewarm(b, tracer) and pipe.join_prewarm(c, tracer)
    pipe.dispatch_ahead(tracer)
    assert len(backend.flushes) == 2 and not pipe.join_prewarm(late, tracer) and not pipe._candidates


@limit(30)
def test_the_two_copies_of_the_plain_archive_replay_are_one():
    here = os.path.dirname(os.path.abspath(__file__))
    mark = "# -- a history archive, replayed plainly"
    mine = open(os.path.join(here, "reference_apply.py")).read()
    theirs = open(os.path.join(here, "..", "benchmarks", "reference_replay.py")).read()
    assert mark in mine and mine[mine.index(mark):] == theirs[theirs.index(mark):]
