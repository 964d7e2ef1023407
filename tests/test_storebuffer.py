"""Write-back entry store buffer (ledger/storebuffer.py).

The buffer replaces per-store SQL on the close path with an authoritative
overlay + one batched flush.  The reference has no such layer — its
EntryFrame writes through (src/ledger/EntryFrame.h:23-79) — so the contract
here is equivalence: a node with ENTRY_WRITE_BUFFER=on must produce
bit-identical ledgers AND bit-identical SQL state to one with it off, for
every entry type, through rollbacks, crossings, deletes, and aggregate
reads.
"""

import sqlite3

import pytest

import stellar_tpu.xdr as X
from stellar_tpu.crypto import SecretKey, strkey
from stellar_tpu.ledger.accountframe import AccountFrame
from stellar_tpu.ledger.delta import LedgerDelta
from stellar_tpu.ledger.entryframe import key_bytes, store_add_or_change
from stellar_tpu.ledger.storebuffer import store_buffer_of
from stellar_tpu.main.application import Application
from stellar_tpu.tx import testutils as T
from stellar_tpu.util import VIRTUAL_TIME, VirtualClock

RC = X.TransactionResultCode


@pytest.fixture
def clock():
    c = VirtualClock(VIRTUAL_TIME)
    yield c
    c.shutdown()


def _dump_entry_tables(db):
    out = {}
    for table, order in (
        ("accounts", "accountid"),
        ("signers", "accountid, publickey"),
        ("trustlines", "accountid, issuer, assetcode"),
        ("offers", "offerid"),
    ):
        out[table] = db.query_all(f"SELECT * FROM {table} ORDER BY {order}")
    return out


class _ScenarioRunner:
    """Drive the same close sequence through two apps (buffer on / off) and
    compare ledger hashes + raw SQL state after every close."""

    def __init__(self, clock, instance_base, cow=True, tmp=None):
        """`tmp`: keep each node's database in a file there, for a reader
        that is not the program (`db_paths`)."""
        self.apps = []
        self.db_paths = []
        for i, buffered in enumerate((True, False)):
            cfg = T.get_test_config(instance_base + i)
            cfg.ENTRY_WRITE_BUFFER = buffered
            cfg.COW_ENTRY_SNAPSHOTS = cow
            cfg.PARANOID_MODE = True  # audit every close on both sides
            if tmp is not None:
                self.db_paths.append(str(tmp / f"node{i}.db"))
                cfg.DATABASE = f"sqlite3://{self.db_paths[-1]}"
            self.apps.append(Application(clock, cfg, new_db=True))

    def close(self, build_txs):
        """build_txs(app, root) -> [TransactionFrame]; closes both apps."""
        results = []
        for app in self.apps:
            lm = app.ledger_manager
            txs = build_txs(app, T.root_key_for(app))
            T.close_ledger_on(
                app, lm.last_closed.header.scpValue.closeTime + 5, txs
            )
            results.append(
                [tx.get_result_code() for tx in txs]
            )
        buf_app, ref_app = self.apps
        assert results[0] == results[1], "tx result codes diverged"
        assert (
            buf_app.ledger_manager.last_closed.hash
            == ref_app.ledger_manager.last_closed.hash
        ), "ledger hash diverged"
        assert _dump_entry_tables(buf_app.database) == _dump_entry_tables(
            ref_app.database
        ), "SQL entry state diverged"
        return results[0]

    def shutdown(self):
        for app in self.apps:
            app.database.close()


@pytest.fixture
def runner(clock):
    r = _ScenarioRunner(clock, 60)
    yield r
    r.shutdown()


def _seq(app, sk):
    """Next usable seqNum for `sk` (current account seq + 1)."""
    from stellar_tpu.ledger.accountframe import AccountFrame

    return AccountFrame.load_account(
        sk.get_public_key(), app.database
    ).get_seq_num() + 1


def test_differential_payments_and_fees(runner):
    a, b = T.get_account("wbuf-a"), T.get_account("wbuf-b")
    runner.close(lambda app, root: [
        T.tx_from_ops(app, root, _seq(app, root), [
            T.create_account_op(a, 10**12), T.create_account_op(b, 10**12),
        ]),
    ])
    codes = runner.close(lambda app, root: [
        T.tx_from_ops(app, a, _seq(app, a), [T.payment_op(b, 10**7)]),
        T.tx_from_ops(app, b, _seq(app, b), [T.payment_op(a, 3 * 10**6)]),
        # failed tx: underfunded payment rolls back mid-close
        T.tx_from_ops(app, a, _seq(app, a) + 1, [T.payment_op(b, 10**15)]),
    ])
    assert codes[:2] == [RC.txSUCCESS, RC.txSUCCESS]
    assert codes[2] == RC.txFAILED


def test_differential_offer_create_and_cross_same_close(runner):
    """tx1 creates an order book, tx2 crosses it IN THE SAME CLOSE — the
    buffered side's load_best_offers must see tx1's pending offers through
    the overlay merge, take them in the identical order, and delete/modify
    identically."""
    a, b = T.get_account("wbuf-sell"), T.get_account("wbuf-buy")
    runner.close(lambda app, root: [
        T.tx_from_ops(app, root, _seq(app, root), [
            T.create_account_op(a, 10**12), T.create_account_op(b, 10**12),
        ]),
    ])

    def mk_usd(app):
        return X.Asset.alphanum4(b"USD", T.root_key_for(app).get_public_key())

    runner.close(lambda app, root: [
        T.tx_from_ops(app, a, _seq(app, a), [T.change_trust_op(mk_usd(app), 10**12)]),
        T.tx_from_ops(app, b, _seq(app, b), [T.change_trust_op(mk_usd(app), 10**12)]),
    ])
    # fund in a separate close: txset apply order is shuffled, so the USD
    # payment must not race b's change_trust within one set
    runner.close(lambda app, root: [
        T.tx_from_ops(app, root, _seq(app, root), [
            T.payment_op(b, 10**10, asset=mk_usd(app)),
        ]),
    ])
    codes = runner.close(lambda app, root: [
        # a sells XLM for USD at three price levels (same close)
        T.tx_from_ops(app, a, _seq(app, a), [
            T.manage_offer_op(X.Asset.native(), mk_usd(app), 10**8, X.Price(2, 1)),
            T.manage_offer_op(X.Asset.native(), mk_usd(app), 10**8, X.Price(3, 1)),
            T.manage_offer_op(X.Asset.native(), mk_usd(app), 10**8, X.Price(4, 1)),
        ]),
        # b crosses: takes level 1 fully and level 2 partially
        T.tx_from_ops(app, b, _seq(app, b), [
            T.manage_offer_op(mk_usd(app), X.Asset.native(), 45 * 10**7,
                              X.Price(1, 3)),
        ]),
    ])
    assert codes == [RC.txSUCCESS, RC.txSUCCESS]
    # and a later close still agrees (residual book state identical)
    codes = runner.close(lambda app, root: [
        T.tx_from_ops(app, b, _seq(app, b), [
            T.manage_offer_op(mk_usd(app), X.Asset.native(), 10**9,
                              X.Price(1, 4)),
        ]),
    ])
    assert codes == [RC.txSUCCESS]


def test_differential_signers_delete_and_inflation(runner):
    """SetOptions signers (the signers side-table), AccountMerge (delete
    batch), and Inflation (aggregate query → flush_through) in closes."""
    a, b = T.get_account("wbuf-sig"), T.get_account("wbuf-victim")
    s1 = T.get_account("wbuf-signer")
    runner.close(lambda app, root: [
        T.tx_from_ops(app, root, _seq(app, root), [
            T.create_account_op(a, 10**12), T.create_account_op(b, 10**11),
        ]),
    ])
    codes = runner.close(lambda app, root: [
        T.tx_from_ops(app, a, _seq(app, a), [
            T.set_options_op(signer=X.Signer(s1.get_public_key(), 1)),
        ]),
        T.tx_from_ops(app, b, _seq(app, b), [T.merge_op(a)]),
    ])
    assert codes == [RC.txSUCCESS, RC.txSUCCESS]
    codes = runner.close(lambda app, root: [
        T.tx_from_ops(app, a, _seq(app, a), [
            T.set_options_op(inflation_dest=a.get_public_key()),
        ]),
    ])
    assert codes == [RC.txSUCCESS]
    # inflation: process_for_inflation aggregates over accounts — the
    # buffered side must flush_through inside the close before tallying
    codes = runner.close(lambda app, root: [
        T.tx_from_ops(app, a, _seq(app, a), [T.payment_op(root, 10**6)]),
        T.tx_from_ops(app, root, _seq(app, root), [T.inflation_op()]),
    ])
    assert codes[0] == RC.txSUCCESS


# -- signer rows are written only where a store changed them ---------------
#
# One world per case and CoW mode: a buffered and a write-through node under
# PARANOID_MODE and every invariant (get_test_config), three accounts of which
# `a` and `b` hold two signers each.  `_SignerWorld.close` compares result
# codes, ledger hashes and SQL dumps of the two nodes (`_ScenarioRunner`),
# then holds each node's `signers` table, read by sqlite3 alone, against the
# signer lists of the entries the program holds.

_SIGNER_ACCOUNTS = ("a", "b", "c")


def _sk(name):
    return T.get_account("wbuf-signers-" + name)


def _strkey(sk_or_pk):
    pk = sk_or_pk.get_public_key() if isinstance(sk_or_pk, SecretKey) else sk_or_pk
    return strkey.to_account_strkey(pk.value)


class _SignerWorld(_ScenarioRunner):
    def __init__(self, clock, tmp, cow):
        super().__init__(clock, 62, cow=cow, tmp=tmp)
        self.accounts = {n: _sk(n) for n in _SIGNER_ACCOUNTS}
        self.statements = []  # of the close or apply under way, per node
        for app, seen in zip(self.apps, ([], [])):
            self.statements.append(seen)
            app.database._conn.set_trace_callback(
                lambda sql, seen=seen: seen.append(sql)
            )
        a, b, c = (self.accounts[n] for n in _SIGNER_ACCOUNTS)
        self.close(lambda app, root: [
            T.tx_from_ops(app, root, _seq(app, root), [
                T.create_account_op(k, 10**12) for k in (a, b, c)
            ]),
        ])
        installed = self.close(lambda app, root: [
            T.tx_from_ops(app, k, _seq(app, k), [
                T.set_options_op(signer=X.Signer(_sk(s).get_public_key(), 1))
                for s in ("s1", "s2")
            ])
            for k in (a, b)
        ])
        assert installed.flush == {
            "account_rows": 2, "rowids_taken": 0, "signer_rows": 4,
            "signer_accounts": 2,
        }

    def signer_statements(self, node):
        """What the node ran against `signers` since the last close or
        apply began, reads left out (the PARANOID audit reloads from SQL)."""
        return [
            sql for sql in self.statements[node]
            if "signers" in sql and not sql.lstrip().upper().startswith("SELECT")
        ]

    def _begin(self):
        for app, seen in zip(self.apps, self.statements):
            seen.clear()
            app.tracer.clear()

    def close(self, build_txs):
        self._begin()
        codes = super().close(build_txs)
        return self._outcome(codes)

    def apply_direct(self, store):
        """`store(app, delta, db)` outside a close, as a bucket apply or a
        test does: on the first node with the store buffer switched on and
        flushed by hand, on the second written through."""
        self._begin()
        flush = None
        for app in self.apps:
            db = app.database
            with db.transaction():
                buf = store_buffer_of(db) if app.config.ENTRY_WRITE_BUFFER else None
                if buf is not None:
                    buf.activate()
                try:
                    delta = LedgerDelta(app.ledger_manager.current.header, db)
                    store(app, delta, db)
                    delta.commit()
                    if buf is not None:
                        flush = buf.flush(db)
                finally:
                    if buf is not None:
                        buf.deactivate()
        assert _dump_entry_tables(self.apps[0].database) == _dump_entry_tables(
            self.apps[1].database
        ), "SQL entry state diverged"
        return self._outcome(None, flush)

    def _outcome(self, codes, flush=None):
        if flush is None:
            spans, _, _ = self.apps[0].tracer.snapshot()
            (flush,) = [s.attrs for s in spans if s.name == "commit.flush"]
        for app, path in zip(self.apps, self.db_paths):
            assert _signers_by_sqlite3(path) == self.signers_held(app)
        return _Outcome(codes, flush)

    def signers_held(self, app):
        """{account: {signer: weight}} of the entries as the program holds
        them (the entry cache's line: the snapshot last stored)."""
        held = {}
        for (aid,) in app.database.query_all("SELECT accountid FROM accounts"):
            pk = X.PublicKey.from_ed25519(strkey.from_account_strkey(aid))
            entry = AccountFrame.load_account(pk, app.database, readonly=True)
            if entry.account.signers:
                held[aid] = {_strkey(s.pubKey): s.weight for s in entry.account.signers}
        return held


class _Outcome:
    def __init__(self, codes, flush):
        self.codes, self.flush = codes, flush


def _signers_by_sqlite3(path):
    con = sqlite3.connect(f"file:{path}?mode=ro", uri=True)
    try:
        rows = con.execute("SELECT accountid, publickey, weight FROM signers").fetchall()
    finally:
        con.close()
    table = {}
    for aid, pk, weight in rows:
        table.setdefault(aid, {})[pk] = weight
    return table


def _set_signer(app, k, name, weight):
    return T.tx_from_ops(app, k, _seq(app, k), [
        T.set_options_op(signer=X.Signer(_sk(name).get_public_key(), weight)),
    ])


def _only_of(w, *names):
    """Every statement either node ran against `signers` names one of
    these accounts, and each of them is named."""
    aids = {_strkey(w.accounts[n]) for n in names}
    for node in (0, 1):
        ran = w.signer_statements(node)
        assert all(any(aid in sql for aid in aids) for sql in ran), ran
        assert all(any(aid in sql for sql in ran) for aid in aids), ran


def _case_payments(w, a, b, c):
    out = w.close(lambda app, root: [
        T.tx_from_ops(app, a, _seq(app, a), [T.payment_op(b, 10**7)]),
        T.tx_from_ops(app, b, _seq(app, b), [T.payment_op(c, 10**6)]),
    ])
    assert out.codes == [RC.txSUCCESS, RC.txSUCCESS]
    assert out.flush == {"account_rows": 3, "rowids_taken": 0, "signer_rows": 0, "signer_accounts": 0}
    _only_of(w)


def _case_add(w, a, b, c):
    out = w.close(lambda app, root: [
        _set_signer(app, a, "s3", 1),
        T.tx_from_ops(app, b, _seq(app, b), [T.payment_op(c, 10**6)]),
    ])
    assert out.codes == [RC.txSUCCESS, RC.txSUCCESS]
    # two rows deleted, three inserted; b's two rows are left alone
    assert out.flush == {"account_rows": 3, "rowids_taken": 0, "signer_rows": 5, "signer_accounts": 1}
    _only_of(w, "a")


def _case_remove(w, a, b, c):
    out = w.close(lambda app, root: [_set_signer(app, a, "s1", 0)])
    assert out.codes == [RC.txSUCCESS]
    assert out.flush == {"account_rows": 1, "rowids_taken": 0, "signer_rows": 3, "signer_accounts": 1}
    assert list(_signers_by_sqlite3(w.db_paths[0])[_strkey(a)]) == [_strkey(_sk("s2"))]


def _case_reweigh(w, a, b, c):
    # the payment's store, after the SET_OPTIONS', finds the list as stored:
    # the slot's mark has to outlive it
    out = w.close(lambda app, root: [
        _set_signer(app, a, "s1", 5),
        T.tx_from_ops(app, a, _seq(app, a) + 1, [T.payment_op(c, 10**6)]),
    ])
    assert out.codes == [RC.txSUCCESS, RC.txSUCCESS]
    assert out.flush == {"account_rows": 2, "rowids_taken": 0, "signer_rows": 4, "signer_accounts": 1}
    assert _signers_by_sqlite3(w.db_paths[0])[_strkey(a)][_strkey(_sk("s1"))] == 5


def _case_change_and_back(w, a, b, c):
    def txs(app, root):
        seq = _seq(app, a)
        return [
            T.tx_from_ops(app, a, seq, [
                T.set_options_op(signer=X.Signer(_sk("s1").get_public_key(), 5)),
            ]),
            T.tx_from_ops(app, a, seq + 1, [
                T.set_options_op(signer=X.Signer(_sk("s1").get_public_key(), 1)),
            ]),
            T.tx_from_ops(app, a, seq + 2, [T.payment_op(c, 10**6)]),
        ]

    before = _signers_by_sqlite3(w.db_paths[0])
    out = w.close(txs)
    assert out.codes == [RC.txSUCCESS] * 3
    # once marked, marked for the close: the rows are written though the
    # list is the stored one again
    assert out.flush == {"account_rows": 2, "rowids_taken": 0, "signer_rows": 4, "signer_accounts": 1}
    assert _signers_by_sqlite3(w.db_paths[0]) == before


def _case_rolled_back(w, a, b, c):
    def txs(app, root):
        seq = _seq(app, a)
        return [
            T.tx_from_ops(app, a, seq, [
                T.set_options_op(signer=X.Signer(_sk("s3").get_public_key(), 1)),
                T.payment_op(b, 10**15),  # underfunded: the SET_OPTIONS unwinds
            ]),
            T.tx_from_ops(app, a, seq + 1, [T.payment_op(b, 10**6)]),
        ]

    before = _signers_by_sqlite3(w.db_paths[0])
    out = w.close(txs)
    assert out.codes == [RC.txFAILED, RC.txSUCCESS]
    assert _signers_by_sqlite3(w.db_paths[0]) == before
    # the rollback erased a's line of the entry cache, so the payment's
    # store has no stored snapshot at hand: written, unchanged
    assert out.flush == {"account_rows": 2, "rowids_taken": 0, "signer_rows": 4, "signer_accounts": 1}
    _only_of(w, "a")


def _new_account(sk, signers, balance=10**10):
    frame = AccountFrame(account_id=sk.get_public_key())
    frame.account.balance = balance
    frame.account.signers = [
        X.Signer(_sk(s).get_public_key(), weight) for s, weight in signers
    ]
    frame.account.numSubEntries = len(signers)
    return frame.entry


def _case_add_or_change(w, a, b, c):
    d = _sk("d")
    w.accounts["d"] = d
    out = w.apply_direct(lambda app, delta, db: store_add_or_change(
        _new_account(d, [("s1", 1), ("s2", 2)]), delta, db
    ))
    # a new account: the one row the flush appends
    assert out.flush == {"account_rows": 1, "rowids_taken": 1, "signer_rows": 2, "signer_accounts": 1}
    _only_of(w, "d")
    assert len(_signers_by_sqlite3(w.db_paths[0])[_strkey(d)]) == 2
    # the same signers under another balance: the row of accounts alone
    out = w.apply_direct(lambda app, delta, db: store_add_or_change(
        _new_account(d, [("s1", 1), ("s2", 2)], balance=10**9), delta, db
    ))
    assert out.flush == {"account_rows": 1, "rowids_taken": 0, "signer_rows": 0, "signer_accounts": 0}
    _only_of(w)
    out = w.apply_direct(lambda app, delta, db: store_add_or_change(
        _new_account(d, [("s2", 2)]), delta, db
    ))
    assert out.flush == {"account_rows": 1, "rowids_taken": 0, "signer_rows": 3, "signer_accounts": 1}
    _only_of(w, "d")


def _case_merged_away(w, a, b, c):
    out = w.close(lambda app, root: [
        T.tx_from_ops(app, b, _seq(app, b), [T.merge_op(a)]),
    ])
    assert out.codes == [RC.txSUCCESS]
    # a is credited and keeps its rows; b's go with its account
    assert out.flush == {"account_rows": 1, "rowids_taken": 0, "signer_rows": 0, "signer_accounts": 0}
    _only_of(w, "b")
    assert _strkey(b) not in _signers_by_sqlite3(w.db_paths[0])


def _case_no_snapshot(w, a, b, c):
    def store(app, delta, db):
        frame = AccountFrame.load_account(a.get_public_key(), db)
        db._entry_cache.erase(key_bytes(frame.get_key()))
        frame.add_balance(-1)
        frame.store_change(delta, db)

    out = w.apply_direct(store)
    # nothing says what SQL holds of a's signers: written as they are
    assert out.flush == {"account_rows": 1, "rowids_taken": 0, "signer_rows": 4, "signer_accounts": 1}
    _only_of(w, "a")
    # a close on a cold cache warms it from SQL before the first store
    for app in w.apps:
        app.database._entry_cache.clear()
    out = w.close(lambda app, root: [
        T.tx_from_ops(app, a, _seq(app, a), [T.payment_op(b, 10**6)]),
    ])
    assert out.flush == {"account_rows": 2, "rowids_taken": 0, "signer_rows": 0, "signer_accounts": 0}
    _only_of(w)


def _case_closes_in_a_row(w, a, b, c):
    def pay(app, root):
        return [
            T.tx_from_ops(app, a, _seq(app, a), [T.payment_op(b, 10**6)]),
            T.tx_from_ops(app, b, _seq(app, b), [T.payment_op(a, 10**5)]),
        ]

    out = w.close(lambda app, root: [_set_signer(app, a, "s3", 1)] + pay(app, root)[1:])
    assert out.flush == {"account_rows": 2, "rowids_taken": 0, "signer_rows": 5, "signer_accounts": 1}
    for _ in range(2):
        out = w.close(pay)
        assert out.flush == {"account_rows": 2, "rowids_taken": 0, "signer_rows": 0, "signer_accounts": 0}
        _only_of(w)
    out = w.close(lambda app, root: [_set_signer(app, b, "s2", 7)] + pay(app, root)[:1])
    assert out.flush == {"account_rows": 2, "rowids_taken": 0, "signer_rows": 4, "signer_accounts": 1}
    _only_of(w, "b")
    assert len(_signers_by_sqlite3(w.db_paths[0])[_strkey(a)]) == 3


_SIGNER_CASES = {
    "payments-among-multisig-accounts": _case_payments,
    "add-a-signer": _case_add,
    "remove-a-signer": _case_remove,
    "change-a-weight": _case_reweigh,
    "change-and-change-back-in-one-close": _case_change_and_back,
    "rolled-back-set-options-then-a-payment": _case_rolled_back,
    "store-add-or-change-with-signers": _case_add_or_change,
    "an-account-merged-away": _case_merged_away,
    "no-cached-previous-snapshot": _case_no_snapshot,
    "two-closes-in-a-row": _case_closes_in_a_row,
}


@pytest.mark.parametrize("cow", [True, False], ids=["cow", "eager-copy"])
@pytest.mark.parametrize("case", list(_SIGNER_CASES))
def test_differential_signer_rows_written_only_where_changed(clock, tmp_path, case, cow):
    w = _SignerWorld(clock, tmp_path, cow)
    try:
        _SIGNER_CASES[case](w, *(w.accounts[n] for n in _SIGNER_ACCOUNTS))
    finally:
        w.shutdown()


class TestBufferMechanics:
    def _buf(self):
        from stellar_tpu.ledger.storebuffer import EntryStoreBuffer

        return EntryStoreBuffer()

    def _key(self, n):
        from stellar_tpu.xdr.entries import LedgerEntryType, PublicKey
        from stellar_tpu.xdr.ledger import LedgerKey, LedgerKeyAccount

        pk = PublicKey.from_ed25519(bytes([n]) * 32)
        return LedgerKey(LedgerEntryType.ACCOUNT, LedgerKeyAccount(pk))

    def test_overlay_and_mark_unwind(self):
        buf = self._buf()
        buf.activate()
        k1, k2 = self._key(1), self._key(2)
        buf.record(b"k1", k1, "v1", object)
        buf.push_mark()
        buf.record(b"k1", k1, "v2", object)  # overwrite inside savepoint
        buf.record(b"k2", k2, None, object)  # delete inside savepoint
        assert buf.get(b"k1") == (True, "v2")
        assert buf.get(b"k2") == (True, None)
        buf.rollback_mark()
        assert buf.get(b"k1") == (True, "v1")  # restored
        assert buf.get(b"k2") == (False, None)  # gone
        buf.deactivate()

    def test_nested_marks_release_keeps_outer_scope(self):
        buf = self._buf()
        buf.activate()
        k1 = self._key(1)
        buf.push_mark()  # outer savepoint
        buf.push_mark()  # inner savepoint
        buf.record(b"k1", k1, "inner", object)
        buf.release_mark()  # inner commits into outer scope
        buf.rollback_mark()  # outer rolls back: inner's write must unwind
        assert buf.get(b"k1") == (False, None)
        buf.deactivate()

    def test_signer_mark_is_sticky_and_rides_the_undo_log(self):
        buf = self._buf()
        buf.activate()
        k1, k2 = self._key(1), self._key(2)
        buf.record(b"k1", k1, "fee", object)  # signers as stored
        buf.record(b"k2", k2, "set-options", object, True)
        buf.push_mark()
        buf.record(b"k1", k1, "set-options", object, True)
        buf.record(b"k2", k2, "payment", object)  # list as last stored
        assert buf._overlay[b"k1"][3] and buf._overlay[b"k2"][3]
        buf.rollback_mark()
        # each slot as it was before the savepoint, mark included
        assert buf._overlay[b"k1"] == (k1, "fee", object, False)
        assert buf._overlay[b"k2"] == (k2, "set-options", object, True)
        buf.record(b"k2", k2, None, object)  # merged away, then created again
        buf.record(b"k2", k2, "created", object)
        assert buf._overlay[b"k2"][3]
        buf.deactivate()

    def test_flush_through_survives_enclosing_rollback(self, clock):
        """Mid-close flush (inflation) inside a savepoint that then rolls
        back: SQL undoes the rows, the undo log restores the overlay."""
        cfg = T.get_test_config(68)
        app = Application(clock, cfg, new_db=True)
        try:
            from stellar_tpu.ledger.accountframe import AccountFrame
            from stellar_tpu.ledger.delta import LedgerDelta
            from stellar_tpu.ledger.storebuffer import store_buffer_of

            from stellar_tpu.ledger.entryframe import key_bytes

            root = T.root_key_for(app)
            db = app.database
            lm = app.ledger_manager
            pk = root.get_public_key()
            balance0 = AccountFrame.load_account(pk, db).get_balance()
            with db.transaction():
                buf = store_buffer_of(db)
                buf.activate()
                try:
                    # pending write made BEFORE the savepoint: must survive
                    # the savepoint's rollback as a pending write
                    delta0 = LedgerDelta(lm.current.header, db)
                    f0 = AccountFrame.load_account(pk, db)
                    f0.account.balance -= 111
                    f0.store_change(delta0, db)
                    kb = key_bytes(f0.get_key())
                    with pytest.raises(RuntimeError, match="boom"):
                        with db.transaction():  # savepoint w/ mark
                            delta = LedgerDelta(lm.current.header, db)
                            f = AccountFrame.load_account(pk, db)
                            f.account.balance -= 12345
                            f.store_change(delta, db)
                            buf.flush_through(db)  # rows land in savepoint
                            assert not buf._overlay
                            raise RuntimeError("boom")
                    # savepoint rolled back: SQL undid the flushed rows and
                    # the undo log re-instated exactly the pre-savepoint
                    # pending state — the in-savepoint -12345 is gone, the
                    # pre-savepoint -111 is pending again
                    hit, pending = buf.get(kb)
                    assert hit
                    assert pending.data.value.balance == balance0 - 111
                    row = db.query_one(
                        "SELECT balance FROM accounts WHERE accountid=?",
                        (root.get_strkey_public(),),
                    )
                    assert row[0] == balance0, "savepoint must undo the flush"
                finally:
                    buf.deactivate()
            db._entry_cache.clear()
            assert AccountFrame.load_account(pk, db).get_balance() == balance0
        finally:
            app.database.close()

    def test_close_uses_buffer_and_skips_per_store_sql(self, clock):
        """The point of the buffer: a buffered close issues no per-entry
        INSERT/UPDATE statements, only the batched flush."""
        cfg = T.get_test_config(69)
        app = Application(clock, cfg, new_db=True)
        try:
            root = T.root_key_for(app)
            a = T.get_account("wbuf-count")
            lm = app.ledger_manager
            from stellar_tpu.ledger.accountframe import AccountFrame

            calls = []
            orig = AccountFrame._persist
            AccountFrame._persist = lambda self, db, insert: calls.append(1)
            try:
                T.close_ledger_on(
                    app,
                    lm.last_closed.header.scpValue.closeTime + 5,
                    [T.tx_from_ops(app, root, _seq(app, root),
                                   [T.create_account_op(a, 10**10)])],
                )
            finally:
                AccountFrame._persist = orig
            assert not calls, "buffered close must not write per-store SQL"
            buf = app.database._store_buffer
            assert buf.n_buffered_writes > 0 and buf.n_flushes == 1
            # the flush landed: rows are queryable post-close
            assert AccountFrame.load_account(a.get_public_key(),
                                             app.database) is not None
        finally:
            app.database.close()
