"""Unit tests for the r21 parallel-apply scheduler internals.

The end-to-end bit-exactness proof lives in tests/test_framecontext.py
(every differential scenario knob-on/off + the engagement/fallback
white-box test) and tests/test_scenarios.py (chaos-class deterministic
replay).  This file pins the pieces in isolation: footprint
classification, the union-find partition, the greedy shard packing, the
FootprintEscape fences on the shard planes, how many threads a close is
sized to, and the bytes of the history rows on every path."""

import types

import pytest

import stellar_tpu.xdr as X
from stellar_tpu.ledger.applysched import (
    ApplyScheduler,
    FootprintEscape,
    ShardEntryCache,
    ShardStoreBuffer,
)
from stellar_tpu.ledger.storebuffer import EntryStoreBuffer
from stellar_tpu.tx import testutils as T
from stellar_tpu.tx.frame import TransactionFrame, _acct_kb

NET = b"\x07" * 32


def frame(source, ops):
    tx = X.Transaction(
        sourceAccount=source.get_public_key(),
        fee=100 * max(1, len(ops)),
        seqNum=1,
        timeBounds=None,
        memo=X.Memo.none(),
        operations=ops,
        ext=0,
    )
    return TransactionFrame(NET, X.TransactionEnvelope(tx, []))


A, B, C = (T.get_account("fp-%d" % i) for i in range(3))


# -- static_footprint classification ----------------------------------------


def test_footprint_bounded_ops():
    fp = frame(A, [T.payment_op(B, 5)]).static_footprint()
    assert fp == {_acct_kb(A.get_public_key()), _acct_kb(B.get_public_key())}
    fp = frame(A, [T.create_account_op(B, 10**10)]).static_footprint()
    assert fp == {_acct_kb(A.get_public_key()), _acct_kb(B.get_public_key())}
    fp = frame(A, [T.merge_op(B)]).static_footprint()
    assert fp == {_acct_kb(A.get_public_key()), _acct_kb(B.get_public_key())}
    # plain set_options touches only the source
    fp = frame(A, [T.set_options_op(master_weight=2)]).static_footprint()
    assert fp == {_acct_kb(A.get_public_key())}
    # an op-level source widens the footprint
    fp = frame(A, [T.payment_op(B, 5, source=C)]).static_footprint()
    assert _acct_kb(C.get_public_key()) in fp and len(fp) == 3


def test_footprint_unbounded_ops_classify_conflicting():
    cny = X.Asset.alphanum4(b"CNY\x00", C.get_public_key())
    price = X.Price(1, 1)
    unbounded = [
        [T.payment_op(B, 5, asset=cny)],
        [T.path_payment_op(B, X.Asset.native(), 10, X.Asset.native(), 10, [])],
        [T.manage_offer_op(X.Asset.native(), cny, 100, price)],
        [T.create_passive_offer_op(X.Asset.native(), cny, 100, price)],
        [T.change_trust_op(cny, 10**9)],
        [T.allow_trust_op(B, b"CNY\x00", True)],
        [T.inflation_op()],
        [T.set_options_op(inflation_dest=B.get_public_key())],
        # one bad op poisons an otherwise-bounded tx
        [T.payment_op(B, 5), T.inflation_op()],
    ]
    for ops in unbounded:
        assert frame(A, ops).static_footprint() is None, ops


# -- partition ---------------------------------------------------------------


def sched():
    return ApplyScheduler(None)  # _partition/_assign never touch the lm


def test_partition_disjoint_pairs_and_chains():
    accts = [T.get_account("pt-%d" % i) for i in range(8)]
    # XOR pairs: (0,1) (2,3) (4,5) (6,7) -> 4 groups, canonical order
    pairs = [frame(accts[i], [T.payment_op(accts[i ^ 1], 1)]) for i in range(8)]
    groups = sched()._partition(pairs)
    assert [sorted(i for i, _tx in g) for g in groups] == [
        [0, 1], [2, 3], [4, 5], [6, 7],
    ]
    # group order is first-tx canonical order, tx identity preserved
    assert groups[0][0] == (0, pairs[0]) and groups[3][1] == (7, pairs[7])
    # a chain (i -> i+1) union-finds into ONE group
    chain = [
        frame(accts[i], [T.payment_op(accts[i + 1], 1)]) for i in range(7)
    ]
    groups = sched()._partition(chain)
    assert len(groups) == 1 and len(groups[0]) == 7


def test_partition_conflicting_tx_poisons_the_set():
    txs = [
        frame(A, [T.payment_op(B, 1)]),
        frame(B, [T.inflation_op()]),
    ]
    assert sched()._partition(txs) is None


def test_partition_is_deterministic():
    accts = [T.get_account("dt-%d" % i) for i in range(6)]
    txs = [frame(accts[i], [T.payment_op(accts[(i + 3) % 6], 1)]) for i in range(6)]
    a = sched()._partition(txs)
    b = sched()._partition(txs)
    assert [[i for i, _ in g] for g in a] == [[i for i, _ in g] for g in b]


# -- greedy shard packing ----------------------------------------------------


def test_assign_balances_largest_first():
    groups = [[None] * n for n in (5, 3, 3, 2, 2, 1)]
    shards = sched()._assign(groups, 2)
    loads = sorted(sum(len(groups[g]) for g in s) for s in shards)
    assert loads == [8, 8]
    # deterministic: same answer twice
    assert sched()._assign(groups, 2) == shards


def test_assign_drops_empty_shards():
    groups = [[None], [None]]
    shards = sched()._assign(groups, 4)
    assert len(shards) == 2 and sorted(g for s in shards for g in s) == [0, 1]


# -- FootprintEscape fences --------------------------------------------------


class _FakeMainCache:
    def __init__(self, d=None):
        self.d = dict(d or {})

    def peek(self, kb):
        return (kb in self.d, self.d.get(kb))

    def contains(self, kb):
        return kb in self.d


def test_shard_cache_fences_and_overlay():
    inside, outside = b"a:in", b"a:out"
    main = _FakeMainCache({inside: "main-entry"})
    cache = ShardEntryCache(main, frozenset([inside]))
    assert cache.peek(inside) == (True, "main-entry")
    cache.put_owned(inside, "shard-entry")
    assert cache.peek(inside) == (True, "shard-entry")
    assert main.d[inside] == "main-entry"  # main plane never written
    for probe in (cache.peek, cache.contains, lambda kb: cache.put_owned(kb, 1)):
        with pytest.raises(FootprintEscape):
            probe(outside)
    with pytest.raises(FootprintEscape):
        cache.clear()
    # erase is deliberately unchecked (rollback during an escape unwind)
    cache.erase(outside)
    cache.erase(inside)
    assert cache.peek(inside) == (True, "main-entry")


def test_shard_buffer_fences_and_mark_rollback():
    inside, outside = b"b:in", b"b:out"
    key = types.SimpleNamespace(type=None)  # record() sniffs key.type
    main = EntryStoreBuffer()
    main.active = True
    main.record(inside, key, "main-slot", None)
    buf = ShardStoreBuffer(main, frozenset([inside]))
    assert buf.get(inside) == (True, "main-slot")
    buf.push_mark()
    buf.record(inside, key, "shard-slot", None)
    assert buf.get(inside) == (True, "shard-slot")
    buf.rollback_mark()
    # rolled back to the main overlay's slot, main untouched
    assert buf.get(inside) == (True, "main-slot")
    assert main.get(inside) == (True, "main-slot")
    with pytest.raises(FootprintEscape):
        buf.get(outside)
    with pytest.raises(FootprintEscape):
        buf.record(outside, key, "x", None)
    with pytest.raises(FootprintEscape):
        buf.flush(None)
    with pytest.raises(FootprintEscape):
        buf.flush_through(None)


# -- sizing: how many interpreter threads apply a set -------------------------


def _node(instance, workers):
    from stellar_tpu.main.application import Application
    from stellar_tpu.util.clock import VIRTUAL_TIME, VirtualClock

    clock = VirtualClock(VIRTUAL_TIME)
    cfg = T.get_test_config(instance)
    cfg.HTTP_PORT = 0
    cfg.APPLY_WORKERS = workers
    return Application.create(clock, cfg, new_db=True), clock


def _close(app, txs):
    lm = app.ledger_manager
    T.close_ledger_on(app, lm.last_closed.header.scpValue.closeTime + 5, txs)
    return lm.last_closed.header.ledgerSeq


def _funded(app, keys, balance=10**9):
    from stellar_tpu.ledger.accountframe import AccountFrame

    root = T.root_key_for(app)
    seq = AccountFrame.load_account(root.get_public_key(), app.database).get_seq_num()
    _close(app, [T.tx_from_ops(app, root, seq + 1, [T.create_account_op(k, balance) for k in keys])])
    return app.ledger_manager.last_closed.header.ledgerSeq << 32


def _pair_payments(app, keys, first, amount=100):
    return [
        T.tx_from_ops(app, k, first + 1, [T.payment_op(keys[i ^ 1], amount)])
        for i, k in enumerate(keys)
    ]


SIZING = {
    # name: (APPLY_WORKERS, sys._is_gil_enabled, cores) -> sized, mode, reason
    "auto-with-the-lock": (0, lambda: True, 13, 1, "serial", "one-worker"),
    "auto-no-such-attribute": (0, None, 13, 1, "serial", "one-worker"),
    "auto-free-threaded-4-cores": (0, lambda: False, 4, 4, "parallel", None),
    "auto-free-threaded-1-core": (0, lambda: False, 1, 1, "serial", "one-worker"),
    "explicit-4": (4, lambda: True, 13, 4, "parallel", None),
    "explicit-1": (1, lambda: False, 13, 1, "serial", "one-worker"),
}


@pytest.mark.parametrize("case", sorted(SIZING))
def test_a_close_is_sized_from_the_interpreter(case, monkeypatch):
    import os
    import sys

    from stellar_tpu.ledger.applysched import apply_scheduler_of, sized_workers

    workers, gil_enabled, cores, sized, mode, reason = SIZING[case]
    if gil_enabled is None:
        monkeypatch.delattr(sys, "_is_gil_enabled", raising=False)
    else:
        monkeypatch.setattr(sys, "_is_gil_enabled", gil_enabled, raising=False)
    monkeypatch.setattr(os, "cpu_count", lambda: cores)
    app, clock = _node(150 + sorted(SIZING).index(case), workers)
    try:
        assert sized_workers(app.config) == sized
        keys = [T.get_account("sz-%d" % i) for i in range(8)]
        first = _funded(app, keys)
        sched = apply_scheduler_of(app.ledger_manager)
        before = dict(sched.stats)
        app.tracer.clear()
        pay = _pair_payments(app, keys, first)
        _close(app, pay)
        assert all(tx.get_result_code().name == "txSUCCESS" for tx in pay)
        names = [s.name for s in app.tracer.spans()]
        assert sched.last_close["mode"] == mode
        if mode == "serial":
            assert sched.last_close == {"mode": "serial", "reason": reason}
            assert sched.stats["closes_serial"] == before["closes_serial"] + 1
            assert sched.stats["closes_parallel"] == before["closes_parallel"]
            # nothing of the threaded plane ran, not even the partition
            assert not {"apply.partition", "apply.shards", "apply.group", "apply.merge"} & set(names)
            (serial,) = [s for s in app.tracer.spans() if s.name == "apply.serial"]
            assert serial.attrs == {"txs": 8, "workers": 1, "reason": reason}
        else:
            assert sched.stats["closes_parallel"] == before["closes_parallel"] + 1
            assert sched.stats["closes_serial"] == before["closes_serial"]
            assert sched.last_close["workers"] == 4 and sched.last_close["groups"] == 4
            assert names.count("apply.group") == 4 and "apply.serial" not in names
        assert apply_scheduler_of(app.ledger_manager).info() == {
            "workers": sized,
            "closes_parallel": sched.stats["closes_parallel"],
            "closes_serial": sched.stats["closes_serial"],
            "reason": reason,
        }
    finally:
        app.graceful_stop()
        clock.shutdown()


# -- history rows: every path writes transaction_row's bytes ------------------


@pytest.mark.parametrize("encoder", ["native", "python"])
def test_transaction_rows_equals_transaction_row(encoder, monkeypatch):
    """Blob lengths of every residue mod 3 (base64's padding), an empty
    meta, an empty set."""
    from stellar_tpu import native
    from stellar_tpu.tx import history as tx_history
    from stellar_tpu.xdr.ledger import TransactionMeta, TransactionResultPair

    if encoder == "native" and native.load_applycore() is None:
        pytest.skip("the _applycore extension did not build here")
    if encoder == "python":
        monkeypatch.setattr(native, "load_applycore", lambda: None)
    assert tx_history.transaction_rows(9, []) == []
    meta = TransactionMeta(0, [])
    items, want = [], []
    for n in range(7):
        txid = bytes([n]) * 32
        env = bytes(range(n)) + b"\xff" * 40
        pair = TransactionResultPair(txid, X.TransactionResult(feeCharged=100 + n))
        items.append((n + 1, txid, env, pair.to_xdr(), meta.to_xdr()))
        want.append(tx_history.transaction_row(txid, 9, n + 1, env, pair, meta))
    got = tx_history.transaction_rows(9, items)
    assert got == want
    assert [[type(col) for col in row] for row in got] == [[str, int, int, str, str, str]] * 7


ROW_SETS = {"pairs-with-a-failed-tx": 6, "one-tx": 1}  # transactions in the set


@pytest.mark.parametrize("encoder", ["native", "python"])
@pytest.mark.parametrize("workers", [0, 4], ids=["sized-to-one", "four-workers"])
@pytest.mark.parametrize("shape", sorted(ROW_SETS))
def test_history_rows_equal_per_tx_rows(shape, workers, encoder, monkeypatch):
    """What a close hands to the txhistory insert — the serial loop's
    batched rows and the shard legs' alike, native encoder and fallback —
    equals tx_history.transaction_row built per transaction from the frame
    and the very meta object apply filled."""
    from stellar_tpu import native
    from stellar_tpu.ledger.applysched import apply_scheduler_of
    from stellar_tpu.tx import history as tx_history

    if encoder == "native" and native.load_applycore() is None:
        pytest.skip("the _applycore extension did not build here")
    if encoder == "python":
        monkeypatch.setattr(native, "load_applycore", lambda: None)
    n = ROW_SETS[shape]
    instance = 160 + 4 * sorted(ROW_SETS).index(shape) + 2 * bool(workers) + (encoder == "python")
    app, clock = _node(instance, workers)
    try:
        keys = [T.get_account("rw-%d" % i) for i in range(max(n, 2))]
        first = _funded(app, keys)
        pay = _pair_payments(app, keys, first)[:n]
        if n > 1:
            # more than the account holds: txFAILED, fee charged, empty meta
            pay[2] = T.tx_from_ops(app, keys[2], first + 1, [T.payment_op(keys[3], 10**12)])

        metas, handed = {}, []
        real_apply = TransactionFrame.apply

        def apply(self, delta, app_, meta=None, tracer=None):
            metas[self.get_contents_hash()] = meta
            return real_apply(self, delta, app_, meta, tracer)

        real_insert = tx_history.insert_transaction_rows

        def insert(db, rows):
            handed.extend(rows)
            real_insert(db, rows)

        monkeypatch.setattr(TransactionFrame, "apply", apply)
        monkeypatch.setattr(tx_history, "insert_transaction_rows", insert)
        seq = _close(app, pay)

        last = apply_scheduler_of(app.ledger_manager).last_close
        if not workers:
            assert last == {"mode": "serial", "reason": "one-worker"}
        elif n == 1:
            assert last == {"mode": "serial", "reason": "single-group"}
        else:
            assert last["mode"] == "parallel" and last["workers"] == 3
        by_index = {row[2]: row for row in handed}
        assert sorted(by_index) == list(range(1, n + 1)) and len(handed) == n
        codes = []
        for row in handed:
            (tx,) = [t for t in pay if t.get_contents_hash().hex() == row[0]]
            codes.append(tx.get_result_code().name)
            meta = metas[tx.get_contents_hash()]
            assert row == tx_history.transaction_row(
                tx.get_contents_hash(), seq, row[2], tx.env_xdr(), tx.get_result_pair(), meta
            )
            if tx.get_result_code().name == "txFAILED":
                assert meta.value == []
        assert sorted(codes) == (["txFAILED"] if n > 1 else []) + ["txSUCCESS"] * (n - (n > 1))
        # and what the database holds is what was handed over
        stored = app.database.query_all(
            "SELECT txid, ledgerseq, txindex, txbody, txresult, txmeta FROM txhistory"
            " WHERE ledgerseq=? ORDER BY txindex", (seq,),
        )
        assert [tuple(r) for r in stored] == [by_index[i] for i in range(1, n + 1)]
    finally:
        app.graceful_stop()
        clock.shutdown()


def test_a_failed_tx_after_its_partners_payment_merges_bit_exact():
    """In a shard, a transaction that fails rolls back and erases the
    shard's cache lines for its accounts; where an earlier transaction of
    the shard had stored them, the merge must not leave the pre-apply line
    in the main cache (the close's cache invariant read it as stale, and
    raised).  Four workers against the serial loop: same hashes, same SQL."""
    from stellar_tpu.ledger.applysched import apply_scheduler_of

    out = []
    for instance, workers in ((176, 0), (177, 4)):
        app, clock = _node(instance, workers)
        try:
            keys = [T.get_account("fl-%d" % i) for i in range(12)]
            first = _funded(app, keys)
            # in every pair the even account pays more than it holds
            pay = [
                T.tx_from_ops(app, k, first + 1, [T.payment_op(keys[i ^ 1], 100 if i & 1 else 10**12)])
                for i, k in enumerate(keys)
            ]
            seq = _close(app, pay)
            codes = [tx.get_result_code().name for tx in pay]
            assert codes == ["txFAILED", "txSUCCESS"] * 6
            order = {
                txid: i
                for txid, i in app.database.query_all(
                    "SELECT txid, txindex FROM txhistory WHERE ledgerseq=?", (seq,)
                )
            }
            at = [order[tx.get_contents_hash().hex()] for tx in pay]
            # the case at stake: the failure applied after its partner's store
            assert any(at[i] > at[i + 1] for i in range(0, 12, 2))
            inv = app.invariants
            assert inv.total_violations == 0, inv.dump_info()
            out.append((
                apply_scheduler_of(app.ledger_manager).last_close["mode"],
                app.ledger_manager.last_closed.hash,
                T.dump_state(app.database),
            ))
        finally:
            app.graceful_stop()
            clock.shutdown()
    (mode_a, hash_a, sql_a), (mode_b, hash_b, sql_b) = out
    assert (mode_a, mode_b) == ("serial", "parallel")
    assert hash_a == hash_b and sql_a == sql_b


def test_signer_rows_written_only_where_changed_merge_bit_exact():
    """The shard planes carry the store buffer's signer mark: a SET_OPTIONS
    in a shard marks its slot, a later payment's store of the same account
    there or a fee charged on the main slot does not clear it, and payments
    among accounts with signers write no signer row on either plane.  Four
    workers against the serial loop, PARANOID: same hashes, same SQL."""
    from stellar_tpu.ledger.applysched import apply_scheduler_of

    def signer(i, weight):
        return T.set_options_op(
            signer=X.Signer(T.get_account("sg-signer-%d" % i).get_public_key(), weight)
        )

    out = []
    for instance, workers in ((178, 0), (179, 4)):
        app, clock = _node(instance, workers)
        app.config.PARANOID_MODE = True
        try:
            keys = [T.get_account("sg-%d" % i) for i in range(12)]
            first = _funded(app, keys)
            flushes = []

            def close(txs):
                app.tracer.clear()
                _close(app, txs)
                assert [tx.get_result_code().name for tx in txs] == ["txSUCCESS"] * len(txs)
                spans, _, _ = app.tracer.snapshot()
                (flush,) = [s.attrs for s in spans if s.name == "commit.flush"]
                flushes.append((flush["signer_accounts"], flush["signer_rows"]))

            close([T.tx_from_ops(app, k, first + 1, [signer(i, 1), signer(i + 100, 1)]) for i, k in enumerate(keys)])
            close(_pair_payments(app, keys, first + 1))
            # the even accounts change a weight, then pay their partner; the
            # odd ones pay theirs
            close(
                [T.tx_from_ops(app, k, first + 3, [signer(i, 3)]) for i, k in enumerate(keys) if not i & 1]
                + [T.tx_from_ops(app, k, first + 4, [T.payment_op(keys[i ^ 1], 7)]) for i, k in enumerate(keys) if not i & 1]
                + [T.tx_from_ops(app, k, first + 3, [T.payment_op(keys[i ^ 1], 9)]) for i, k in enumerate(keys) if i & 1]
            )
            assert flushes == [(12, 24), (0, 0), (6, 24)]
            assert app.invariants.total_violations == 0, app.invariants.dump_info()
            out.append((
                apply_scheduler_of(app.ledger_manager).last_close["mode"],
                app.ledger_manager.last_closed.hash,
                T.dump_state(app.database),
            ))
        finally:
            app.graceful_stop()
            clock.shutdown()
    (mode_a, hash_a, sql_a), (mode_b, hash_b, sql_b) = out
    assert (mode_a, mode_b) == ("serial", "parallel")
    assert hash_a == hash_b and sql_a == sql_b
    assert len(sql_a["signers"]) == 24
