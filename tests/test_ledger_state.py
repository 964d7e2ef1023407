"""Database + frames + delta tests (reference style: ledger tests against
in-memory sqlite, SURVEY.md §4 layer 3)."""

import pytest

import stellar_tpu.xdr as X
from stellar_tpu.crypto import SecretKey
from stellar_tpu.database.database import Database
from stellar_tpu.ledger import (
    AccountFrame,
    LedgerDelta,
    LedgerHeaderFrame,
    OfferFrame,
    TrustFrame,
)
from stellar_tpu.main.persistentstate import PersistentState


@pytest.fixture
def db():
    d = Database("sqlite3://:memory:")
    d.initialize()
    yield d
    d.close()


@pytest.fixture
def header():
    h = X.LedgerHeader(ledgerSeq=2, baseFee=100, baseReserve=100000000)
    return h


def mk_account(i):
    return SecretKey.pseudo_random_for_testing(i).get_public_key()


class FakeLM:
    base_reserve = 100000000

    def get_min_balance(self, owner_count):
        return (2 + owner_count) * self.base_reserve


class TestDatabase:
    def test_nested_transactions(self, db):
        PersistentState.drop_all(db)
        ps = PersistentState(db)
        with db.transaction():
            ps.set_state("a", "1")
            try:
                with db.transaction():
                    ps.set_state("a", "2")
                    raise RuntimeError("inner fails")
            except RuntimeError:
                pass
            assert ps.get_state("a") == "1"  # inner rolled back
        assert ps.get_state("a") == "1"  # outer committed

    def test_outer_rollback(self, db):
        ps = PersistentState(db)
        try:
            with db.transaction():
                ps.set_state("x", "1")
                raise RuntimeError()
        except RuntimeError:
            pass
        assert ps.get_state("x") is None

    def test_schema_version(self, db):
        assert db.get_schema_version() == 2


class TestAccountFrame:
    def test_store_load_roundtrip(self, db, header):
        aid = mk_account(1)
        delta = LedgerDelta(header, db)
        af = AccountFrame(account_id=aid)
        af.set_balance(1000000000)
        af.set_seq_num(2 << 32)
        af.account.homeDomain = "example.com"
        af.account.signers = [X.Signer(mk_account(2), 5)]
        af.store_add(delta, db)
        AccountFrame.cache_of(db).clear()
        back = AccountFrame.load_account(aid, db)
        assert back is not None
        assert back.get_balance() == 1000000000
        assert back.get_seq_num() == 2 << 32
        assert back.account.homeDomain == "example.com"
        assert back.account.signers == [X.Signer(mk_account(2), 5)]
        assert back.last_modified == 2
        assert back.entry == af.entry

    def test_load_missing_returns_none_and_caches(self, db):
        assert AccountFrame.load_account(mk_account(9), db) is None
        assert AccountFrame.load_account(mk_account(9), db) is None

    def test_bulk_warm_cache_matches_point_loads(self, db, header):
        """AccountFrame.bulk_warm_cache (the big-ledger close prewarm)
        must cache entries identical to load_account's — including
        signers, inflationDest, and known-absent accounts."""
        delta = LedgerDelta(header, db)
        ids = []
        for i in range(1, 8):
            aid = mk_account(i)
            af = AccountFrame(account_id=aid)
            af.set_balance(10**7 * i)
            af.set_seq_num(i << 32)
            if i % 2:
                af.account.signers = [X.Signer(mk_account(20 + i), i)]
            if i % 3 == 0:
                af.account.inflationDest = mk_account(30 + i)
            af.store_add(delta, db)
            ids.append(aid)
        ghost = mk_account(99)
        # point-load ground truth with a cold cache
        AccountFrame.cache_of(db).clear()
        truth = {}
        for aid in ids:
            truth[aid.value] = AccountFrame.load_account(aid, db).entry
        # bulk path, cold cache again
        cache = AccountFrame.cache_of(db)
        cache.clear()
        cache.hits = cache.misses = 0
        AccountFrame.bulk_warm_cache(db, ids + [ghost])
        for aid in ids:
            back = AccountFrame.load_account(aid, db)
            assert back.entry == truth[aid.value]
        assert AccountFrame.load_account(ghost, db) is None
        # every post-warm load was a cache hit: no point SELECTs ran
        assert cache.misses == 0 and cache.hits == len(ids) + 1

    def test_thresholds_defaults(self, db):
        af = AccountFrame(account_id=mk_account(1))
        assert af.get_master_weight() == 1
        assert af.get_low_threshold() == 0
        assert af.get_medium_threshold() == 0
        assert af.get_high_threshold() == 0

    def test_min_balance_and_subentries(self, db):
        lm = FakeLM()
        af = AccountFrame(account_id=mk_account(1))
        af.set_balance(3 * lm.base_reserve)
        assert af.get_minimum_balance(lm) == 2 * lm.base_reserve
        assert af.add_num_entries(1, lm)  # needs 3 reserves, has exactly 3
        assert not af.add_num_entries(1, lm)  # needs 4, has 3
        assert af.add_num_entries(-1, lm)  # decrease always ok

    def test_balance_cannot_go_negative(self):
        af = AccountFrame(account_id=mk_account(1))
        af.set_balance(10)
        assert not af.add_balance(-11)
        assert af.add_balance(-10)
        assert af.get_balance() == 0


class TestTrustAndOfferFrames:
    def test_trustline_roundtrip(self, db, header):
        aid = mk_account(1)
        issuer = mk_account(2)
        asset = X.Asset.alphanum4(b"USD", issuer)
        delta = LedgerDelta(header, db)
        tf = TrustFrame.make(aid, asset)
        tf.trust_line.limit = 500
        tf.set_authorized(True)
        tf.store_add(delta, db)
        TrustFrame.cache_of(db).clear()
        back = TrustFrame.load_trust_line(aid, asset, db)
        assert back.trust_line.limit == 500
        assert back.is_authorized()
        assert back.add_balance(400)
        assert not back.add_balance(200)  # over limit
        assert back.get_max_amount_receive() == 100

    def test_best_offers_ordering(self, db, header):
        delta = LedgerDelta(header, db)
        usd = X.Asset.alphanum4(b"USD", mk_account(50))
        native = X.Asset.native()
        prices = [(3, 2), (1, 1), (2, 1), (1, 1)]
        for i, (n, d) in enumerate(prices):
            op = X.ManageOfferOp(native, usd, 100, X.Price(n, d), i + 1)
            of = OfferFrame.from_manage_op(mk_account(i), op)
            of.store_add(delta, db)
        best = OfferFrame.load_best_offers(10, 0, native, usd, db)
        got = [(o.get_price().n, o.get_price().d, o.get_offer_id()) for o in best]
        # cheapest first; ties broken by offerid (determinism!)
        assert got == [(1, 1, 2), (1, 1, 4), (3, 2, 1), (2, 1, 3)]

    def test_offer_delete(self, db, header):
        delta = LedgerDelta(header, db)
        usd = X.Asset.alphanum4(b"USD", mk_account(50))
        op = X.ManageOfferOp(X.Asset.native(), usd, 100, X.Price(1, 1), 7)
        of = OfferFrame.from_manage_op(mk_account(1), op)
        of.store_add(delta, db)
        of.store_delete(delta, db)
        assert OfferFrame.load_offer(mk_account(1), 7, db) is None


class TestLedgerDelta:
    def test_changes_meta(self, db, header):
        delta = LedgerDelta(header, db)
        af = AccountFrame(account_id=mk_account(1))
        af.set_balance(5)
        af.store_add(delta, db)
        af.set_balance(6)
        af.store_change(delta, db)
        changes = delta.get_changes()
        # created-then-modified collapses to one CREATED with latest state
        assert len(changes) == 1
        assert changes[0].type == X.LedgerEntryChangeType.LEDGER_ENTRY_CREATED
        assert changes[0].value.data.value.balance == 6

    def test_nested_commit_merges(self, db, header):
        outer = LedgerDelta(header, db)
        inner = LedgerDelta(outer=outer)
        af = AccountFrame(account_id=mk_account(1))
        af.store_add(inner, db)
        inner.commit()
        assert len(outer.get_live_entries()) == 1

    def test_nested_rollback_discards(self, db, header):
        outer = LedgerDelta(header, db)
        inner = LedgerDelta(outer=outer)
        af = AccountFrame(account_id=mk_account(1))
        af.store_add(inner, db)
        inner.rollback()
        assert outer.get_live_entries() == []

    def test_header_commit(self, db, header):
        delta = LedgerDelta(header, db)
        delta.generate_id()
        delta.generate_id()
        assert header.idPool == 0  # not yet committed
        delta.commit()
        assert header.idPool == 2

    def test_delete_then_live_entries(self, db, header):
        delta = LedgerDelta(header, db)
        af = AccountFrame(account_id=mk_account(1))
        af.store_add(delta, db)
        af.store_delete(delta, db)
        assert delta.get_live_entries() == []
        assert delta.get_dead_entries() == []  # net nothing

    def test_paranoid_check_against_database(self, db, header):
        delta = LedgerDelta(header, db)
        af = AccountFrame(account_id=mk_account(1))
        af.set_balance(123)
        af.store_add(delta, db)
        delta.check_against_database(db)  # must not raise
        # now corrupt the DB behind the delta's back
        db.execute("UPDATE accounts SET balance=999")
        with pytest.raises(RuntimeError):
            delta.check_against_database(db)


class TestLedgerHeaderFrame:
    def test_store_and_load(self, db):
        h = X.LedgerHeader(ledgerSeq=1, totalCoins=10**17)
        f = LedgerHeaderFrame(h)
        f.store_insert(db)
        by_seq = LedgerHeaderFrame.load_by_sequence(db, 1)
        assert by_seq.header == h
        by_hash = LedgerHeaderFrame.load_by_hash(db, f.get_hash())
        assert by_hash.header == h

    def test_from_previous_links_hash_chain(self, db):
        h1 = LedgerHeaderFrame(X.LedgerHeader(ledgerSeq=1))
        h2 = LedgerHeaderFrame.from_previous(h1)
        assert h2.header.ledgerSeq == 2
        assert h2.header.previousLedgerHash == h1.get_hash()


class TestCoinConservation:
    """Property test: across random op-mix ledgers, native coins are
    conserved — sum(account balances) + feePool == totalCoins
    (the reference enforces this shape via inflation/fee accounting in
    LedgerManagerImpl; here it pins our delta/fee/apply plumbing)."""

    def test_random_ops_conserve_coins(self):
        import random

        from stellar_tpu.herder.ledgerclose import LedgerCloseData
        from stellar_tpu.herder.txset import TxSetFrame
        from stellar_tpu.main.application import Application
        from stellar_tpu.tx import testutils as T
        from stellar_tpu.util.clock import VirtualClock
        from stellar_tpu.xdr import txs as X
        from stellar_tpu.xdr.ledger import StellarValue

        rng = random.Random(77)
        clock = VirtualClock()
        app = Application.create(clock, T.get_test_config(78), new_db=True)
        try:
            lm = app.ledger_manager
            root = T.root_key_for(app)
            keys = [T.get_account(i + 1) for i in range(6)]
            seqs = {}

            def conserved():
                total = app.database.query_one(
                    "SELECT SUM(balance) FROM accounts"
                )[0]
                hdr = lm.last_closed.header
                assert total + hdr.feePool == hdr.totalCoins, (
                    total, hdr.feePool, hdr.totalCoins
                )

            def close(txs):
                txset = TxSetFrame(lm.last_closed.hash, txs)
                txset.sort_for_hash()
                txset.trim_invalid(app)
                sv = StellarValue(
                    txset.get_contents_hash(),
                    lm.last_closed.header.scpValue.closeTime + 5, [], 0
                )
                lm.close_ledger(
                    LedgerCloseData(lm.current.header.ledgerSeq, txset, sv)
                )

            # seed accounts
            rseq = T.root_seq_for = app.database.query_one(
                "SELECT seqnum FROM accounts WHERE balance = ("
                "SELECT MAX(balance) FROM accounts)")[0]
            txs = []
            for k in keys:
                rseq += 1
                txs.append(T.tx_from_ops(
                    app, root, rseq, [T.create_account_op(k, 10**10)]))
            close(txs)
            conserved()
            created = lm.last_closed.header.ledgerSeq
            for k in keys:
                seqs[k.get_strkey_public()] = created << 32

            # 6 ledgers of random payments/creates/merges-less mix
            for _ in range(6):
                txs = []
                for _ in range(rng.randrange(3, 9)):
                    src = rng.choice(keys)
                    dst = rng.choice([k for k in keys if k is not src])
                    sk = src.get_strkey_public()
                    seqs[sk] += 1
                    amt = rng.randrange(1, 10**7)
                    txs.append(T.tx_from_ops(
                        app, src, seqs[sk], [T.payment_op(dst, amt)]))
                close(txs)
                conserved()
        finally:
            app.graceful_stop()
            clock.shutdown()


class TestReadonlyLoads:
    """Read-only loads share the cached entry (no defensive copy) and are
    store-guarded — the validation path's 3-loads-per-tx never mutate
    (PROFILE.md round-5 close split)."""

    def _stored(self, db, header, i=31):
        aid = mk_account(i)
        delta = LedgerDelta(header, db)
        af = AccountFrame(account_id=aid)
        af.set_balance(10**9)
        af.set_seq_num(1 << 32)
        af.store_add(delta, db)
        return aid

    def test_readonly_hit_shares_cache_entry(self, db, header):
        aid = self._stored(db, header)
        ro = AccountFrame.load_account(aid, db, readonly=True)
        rw = AccountFrame.load_account(aid, db)
        assert ro.get_balance() == rw.get_balance() == 10**9
        # rw owns a private copy; ro shares the cache line
        assert rw.entry is not ro.entry
        ro2 = AccountFrame.load_account(aid, db, readonly=True)
        assert ro2.entry is ro.entry

    def test_readonly_store_is_refused(self, db, header):
        aid = self._stored(db, header, 32)
        ro = AccountFrame.load_account(aid, db, readonly=True)
        delta = LedgerDelta(header, db)
        with pytest.raises(RuntimeError, match="read-only"):
            ro.store_change(delta, db)
        with pytest.raises(RuntimeError, match="read-only"):
            ro.store_delete(delta, db)

    def test_readonly_refuses_store_on_cold_load_too(self, db, header):
        # identical semantics hit or miss: a mutation that "works" only on
        # cold loads would be a hidden bug
        aid = self._stored(db, header, 33)
        AccountFrame.cache_of(db).clear()
        ro = AccountFrame.load_account(aid, db, readonly=True)
        delta = LedgerDelta(header, db)
        with pytest.raises(RuntimeError, match="read-only"):
            ro.store_change(delta, db)

    def test_mutable_load_still_isolated_from_cache(self, db, header):
        aid = self._stored(db, header, 34)
        rw = AccountFrame.load_account(aid, db)
        rw.account.balance = 7  # never stored
        again = AccountFrame.load_account(aid, db, readonly=True)
        assert again.get_balance() == 10**9


class TestLedgerHeaderPersistence:
    """LedgerHeaderTests.cpp:22-57 'ledgerheader': a closed ledger's header
    survives an application restart from the same on-disk DB, and loads
    back by hash and by sequence."""

    def test_header_survives_restart(self, tmp_path):
        from stellar_tpu.herder.ledgerclose import LedgerCloseData
        from stellar_tpu.herder.txset import TxSetFrame
        from stellar_tpu.ledger.headerframe import LedgerHeaderFrame
        from stellar_tpu.main.application import Application
        from stellar_tpu.tx import testutils as T
        from stellar_tpu.util.clock import VirtualClock
        from stellar_tpu.xdr.ledger import StellarValue

        cfg = T.get_test_config(55)
        cfg.DATABASE = f"sqlite3://{tmp_path}/header.db"

        clock = VirtualClock()
        app = Application.create(clock, cfg, new_db=True)
        lm = app.ledger_manager
        txset = TxSetFrame(lm.last_closed.hash)
        sv = StellarValue(txset.get_contents_hash(), 1, [], 0)
        lm.close_ledger(
            LedgerCloseData(lm.current.header.ledgerSeq, txset, sv)
        )
        saved_hash = lm.last_closed.hash
        saved_seq = lm.last_closed.header.ledgerSeq
        app.graceful_stop()
        clock.shutdown()

        clock2 = VirtualClock()
        cfg2 = T.get_test_config(55)
        cfg2.DATABASE = f"sqlite3://{tmp_path}/header.db"
        cfg2.FORCE_SCP = False
        app2 = Application.create(clock2, cfg2, new_db=False)
        try:
            app2.start()  # loadLastKnownLedger
            lcl = app2.ledger_manager.last_closed
            assert lcl.hash == saved_hash
            assert lcl.header.ledgerSeq == saved_seq

            by_hash = LedgerHeaderFrame.load_by_hash(app2.database, saved_hash)
            assert by_hash is not None
            assert by_hash.get_hash() == saved_hash
            by_seq = LedgerHeaderFrame.load_by_sequence(app2.database, saved_seq)
            assert by_seq is not None
            assert by_seq.get_hash() == saved_hash
        finally:
            app2.graceful_stop()
            clock2.shutdown()
