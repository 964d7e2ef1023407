"""A set's accounts reach the entry cache in bulk before anything reads one
of them (ISSUE 43): ``TxSetFrame.warm_accounts`` runs where the set's
signature triples are first collected (``site`` ``collect``) and again before
a close applies the set (``site`` ``close``); whoever comes first pays the
loads, and ``warm_asked`` counts the set's accounts once.
"""

import pytest

from stellar_tpu.crypto import strkey
from stellar_tpu.herder.ledgerclose import LedgerCloseData
from stellar_tpu.herder.txset import TxSetFrame
from stellar_tpu.ledger.accountframe import AccountFrame
from stellar_tpu.ledger.delta import LedgerDelta
from stellar_tpu.ledger.entryframe import entry_cache_of, key_bytes
from stellar_tpu.ledger.storebuffer import store_buffer_of
from stellar_tpu.main.application import Application
from stellar_tpu.tx import testutils as T
from stellar_tpu.util.clock import VirtualClock
from stellar_tpu.xdr.ledger import StellarValue

ACCOUNTS = 48
# the tests' invariants re-read every account a close changed or created from
# SQL, past the cache (``load_fresh_entry``): as many more ``sql_loads`` a close
FRESH = ACCOUNTS + ACCOUNTS // 4


def make_app(instance: int):
    clock = VirtualClock()
    return Application(clock, T.get_test_config(instance), new_db=True), clock


def stop(app, clock):
    app.database.close()
    clock.shutdown()


def fund(app, keys):
    """One close that creates ``keys``: -> the first sequence number of each."""
    root = T.root_key_for(app)
    seq = AccountFrame.load_account(root.get_public_key(), app.database).get_seq_num()
    T.close_ledger_on(app, 10, [T.tx_from_ops(app, root, seq + 1, [T.create_account_op(k, 10**10) for k in keys])])
    return app.ledger_manager.last_closed.header.ledgerSeq << 32


def payments(app, keys, first: int, r: int):
    """Every account pays its neighbour, and every fourth creates an account."""
    txs = []
    for i, k in enumerate(keys):
        op = T.payment_op(keys[i ^ 1], 100 + r)
        if i % 4 == 0:
            op = T.create_account_op(T.get_account(9000 + 100 * r + i), 10**9)
        txs.append(T.tx_from_ops(app, k, first + 1 + r, [op]))
    return txs


def ledger_data_of(app, txs) -> LedgerCloseData:
    lm = app.ledger_manager
    txset = TxSetFrame(lm.last_closed.hash, list(txs))
    txset.sort_for_hash()
    sv = StellarValue(txset.get_contents_hash(), lm.last_closed.header.scpValue.closeTime + 5, [], 0)
    return LedgerCloseData(lm.current.header.ledgerSeq, txset, sv)


def warms(app):
    return [(s.attrs["site"], s) for s in app.tracer.spans() if s.name == "accounts.warm"]


@pytest.fixture
def funded():
    app, clock = make_app(191)
    try:
        keys = [T.get_account(7300 + i) for i in range(ACCOUNTS)]
        first = fund(app, keys)
        yield app, keys, first
    finally:
        stop(app, clock)


def test_a_validated_set_is_warmed_at_the_collect_and_found_at_the_close(funded):
    app, keys, first = funded
    cache = entry_cache_of(app.database)
    ld = ledger_data_of(app, payments(app, keys, first, 0))
    ids = ld.tx_set.collect_account_ids()
    assert len(ids) == ACCOUNTS + ACCOUNTS // 4
    cache.clear()
    app.tracer.clear()
    asked0, loads0, misses0 = cache.warm_asked, cache.sql_loads, cache.misses
    assert ld.tx_set.check_valid(app)
    ((site, warm),) = warms(app)
    spans = app.tracer.spans()
    (validate,) = [s for s in spans if s.name == "txset.validate"]
    (collect,) = [s for s in spans if s.name == "sig.collect"]
    assert site == "collect" and warm.parent == validate.sid == collect.parent
    # the warm ends before the collect starts: the two partition the work
    assert warm.end <= collect.start
    assert warm.attrs["asked"] == warm.attrs["missed"] == len(ids)
    assert warm.attrs["rows"] == ACCOUNTS and warm.attrs["selects"] == 1
    # every load of the collect and of the validity walk was a line
    assert cache.sql_loads - loads0 == len(ids) and cache.misses == misses0
    app.ledger_manager.close_ledger(ld)
    (_, (site, at_close)) = warms(app)
    assert site == "close" and at_close.attrs["asked"] == len(ids) and at_close.attrs["missed"] == 0
    assert at_close.attrs["selects"] == at_close.attrs["rows"] == 0
    # asked once a set, asked of SQL once an account
    assert cache.warm_asked - asked0 == len(ids) and cache.sql_loads - loads0 == len(ids) + FRESH
    assert all(tx.get_result_code().name == "txSUCCESS" for tx in ld.tx_set.transactions)


def test_a_set_closed_without_validation_is_warmed_by_the_close(funded):
    app, keys, first = funded
    cache = entry_cache_of(app.database)
    ld = ledger_data_of(app, payments(app, keys, first, 0))
    n = len(ld.tx_set.collect_account_ids())
    cache.clear()
    app.tracer.clear()
    asked0, loads0 = cache.warm_asked, cache.sql_loads
    app.ledger_manager.close_ledger(ld)
    (site0, at_close), (site1, at_collect) = warms(app)
    spans = {s.sid: s for s in app.tracer.spans()}
    assert (site0, site1) == ("close", "collect")
    assert spans[at_close.parent].name == "ledger.close" and spans[at_collect.parent].name == "close.sig_flush"
    assert at_close.attrs["missed"] == n and at_close.attrs["rows"] == ACCOUNTS
    # the close's own prewarm asks under the live store buffer and finds all
    assert at_collect.attrs["asked"] == n and at_collect.attrs["missed"] == 0
    assert cache.warm_asked - asked0 == n and cache.sql_loads - loads0 == n + FRESH


def test_a_cleared_cache_is_reloaded_by_the_close_and_counted_once(funded):
    """Nothing is skipped on the strength of a memo: the close's ask reloads
    what went since the collect, and ``sql_loads`` says so."""
    app, keys, first = funded
    cache = entry_cache_of(app.database)
    ld = ledger_data_of(app, payments(app, keys, first, 0))
    n = len(ld.tx_set.collect_account_ids())
    assert ld.tx_set.check_valid(app)
    cache.clear()
    app.tracer.clear()
    asked0, loads0 = cache.warm_asked, cache.sql_loads
    app.ledger_manager.close_ledger(ld)
    ((site, at_close),) = warms(app)  # the triples were a memo hit: no second collect
    assert site == "close" and at_close.attrs["missed"] == n
    assert cache.warm_asked == asked0 and cache.sql_loads - loads0 == n + FRESH
    assert all(tx.get_result_code().name == "txSUCCESS" for tx in ld.tx_set.transactions)


@pytest.mark.parametrize("change", ["add_transaction", "remove_tx"])
def test_a_changed_set_drops_the_id_memo_with_the_triples_memo(funded, change):
    app, keys, first = funded
    txs = payments(app, keys, first, 0)
    extra = txs.pop(0)  # creates an account nobody else names
    txset = TxSetFrame(app.ledger_manager.last_closed.hash, txs)
    txset.sort_for_hash()
    assert txset.check_valid(app)
    ids = txset.collect_account_ids()
    assert txset.collect_account_ids() is ids and txset._triples_memo is not None and txset._warm_counted
    if change == "add_transaction":
        txset.add_transaction(extra)
    else:
        txset.remove_tx(txs[3])
    assert txset._triples_memo is None and txset._account_ids_memo is None and not txset._warm_counted
    again = txset.collect_account_ids()
    if change == "add_transaction":
        assert again == ids | {extra.envelope.tx.operations[0].body.value.destination} and len(again) == len(ids) + 1
    else:
        # keys[4] created an account: that one is no longer touched, keys[4] is still paid
        assert again < ids and len(again) == len(ids) - 1 and txs[3].get_source_id() in again
    # the changed set is another set: asked again
    cache = entry_cache_of(app.database)
    asked0 = cache.warm_asked
    txset.trim_invalid(app)
    assert cache.warm_asked - asked0 == len(again)


def test_bulk_warm_leaves_a_pending_write_to_the_store_buffer(funded):
    """The store buffer is live and holds a write for a key the cache has
    evicted: the warm neither reads its (stale) SQL row nor puts a line."""
    app, keys, _first = funded
    db, lm = app.database, app.ledger_manager
    cache = entry_cache_of(db)
    pk, other = keys[0].get_public_key(), keys[1].get_public_key()
    ghost = T.get_account(9999).get_public_key()
    balance0 = AccountFrame.load_account(pk, db).get_balance()
    with db.transaction():
        buf = store_buffer_of(db)
        buf.activate()
        try:
            delta = LedgerDelta(lm.current.header, db)
            f = AccountFrame.load_account(pk, db)
            f.account.balance -= 111
            f.store_change(delta, db)
            kb = key_bytes(f.get_key())
            cache.clear()
            loads0 = cache.sql_loads
            did = AccountFrame.bulk_warm_cache(db, [pk, other, ghost])
            # the pending key is the buffer's: not asked of SQL, no line
            assert did == {"asked": 3, "missed": 2, "selects": 1, "rows": 1}
            assert cache.sql_loads - loads0 == 2 and not cache.contains(kb)
            assert db.query_one("SELECT balance FROM accounts WHERE accountid=?", (strkey.to_account_strkey(pk.value),))[0] == balance0
            for readonly in (True, False):
                assert AccountFrame.load_account(pk, db, readonly=readonly).get_balance() == balance0 - 111
            assert AccountFrame.load_account(other, db).get_balance() == 10**10
            assert AccountFrame.load_account(ghost, db) is None
            assert cache.sql_loads - loads0 == 2
        finally:
            buf.deactivate()
    cache.clear()


def test_ledger_hashes_equal_a_plain_nodes_with_the_cache_under_a_set():
    """``check_valid`` + close with 16 cache lines against sets of 60
    accounts, beside a node that only closes with every account a line:
    same hashes, same rows, same result codes."""
    (small, c0), (plain, c1) = make_app(192), make_app(193)
    try:
        keys = [T.get_account(7300 + i) for i in range(ACCOUNTS)]
        firsts = [fund(a, keys) for a in (small, plain)]
        assert firsts[0] == firsts[1]
        cache = entry_cache_of(small.database)
        cache.CAPACITY = 16
        evictions0 = cache.evictions
        for r in range(4):
            codes = []
            for app in (small, plain):
                ld = ledger_data_of(app, payments(app, keys, firsts[0], r))
                if app is small:
                    assert ld.tx_set.check_valid(app)
                app.ledger_manager.close_ledger(ld)
                codes.append([tx.get_result_code().name for tx in ld.tx_set.transactions])
            assert codes[0] == codes[1] and set(codes[0]) == {"txSUCCESS"}
            assert small.ledger_manager.last_closed.hash == plain.ledger_manager.last_closed.hash
        assert T.dump_state(small.database) == T.dump_state(plain.database)
        assert len(cache._map) == 16 and cache.evictions - evictions0 > 4 * ACCOUNTS
        # the collect's warm could not keep a set's lines: the close reloaded
        at_close = [s for site, s in warms(small) if site == "close"]
        assert at_close and all(s.attrs["missed"] > 0 for s in at_close)
        assert small.invariants.total_violations == plain.invariants.total_violations == 0
    finally:
        stop(small, c0)
        stop(plain, c1)
