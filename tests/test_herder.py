"""Herder tests (reference: src/herder/HerderTests.cpp).

Standalone single-Application style: a self-quorum validator drives SCP
through nomination → ballot → externalize → ledger close, with real
signatures, real txsets, and a virtual clock — no overlay.
"""

from __future__ import annotations

import pytest

from stellar_tpu.herder import (
    EXP_LEDGER_TIMESPAN_SECONDS,
    TX_STATUS_DUPLICATE,
    TX_STATUS_ERROR,
    TX_STATUS_PENDING,
    Herder,
)
from stellar_tpu.ledger.accountframe import AccountFrame
from stellar_tpu.main.application import Application
from stellar_tpu.tx import testutils as T
from stellar_tpu.util import VIRTUAL_TIME, VirtualClock


def make_scp_app(clock, instance: int = 0):
    """Application + Herder wired for live (non-manual) consensus."""
    cfg = T.get_test_config(instance)
    cfg.MANUAL_CLOSE = False
    app = Application(clock, cfg, new_db=True)
    app.herder = Herder(app)
    return app


def root_seq(app):
    root = T.root_key_for(app)
    return AccountFrame.load_account(root.get_public_key(), app.database).get_seq_num()


def create_account_tx(app, dest, balance):
    root = T.root_key_for(app)
    seq = max(root_seq(app), app.herder.get_max_seq_in_pending_txs(root.get_public_key()))
    return T.tx_from_ops(app, root, seq + 1, [T.create_account_op(dest, balance)])


def load_or_none(app, key):
    return AccountFrame.load_account(key.get_public_key(), app.database)


@pytest.fixture()
def clock():
    c = VirtualClock(VIRTUAL_TIME)
    yield c
    c.shutdown()


class TestStandaloneConsensus:
    def test_empty_ledgers_close_on_cadence(self, clock):
        app = make_scp_app(clock)
        app.herder.bootstrap()
        lm = app.ledger_manager

        assert clock.crank_until(lambda: lm.get_last_closed_ledger_num() >= 2, 30)
        # next close happens one EXP_LEDGER_TIMESPAN later
        t2 = clock.now()
        assert clock.crank_until(lambda: lm.get_last_closed_ledger_num() >= 3, 30)
        assert clock.now() - t2 >= EXP_LEDGER_TIMESPAN_SECONDS - 1

    def test_create_account_through_consensus(self, clock):
        app = make_scp_app(clock)
        app.herder.bootstrap()
        dest = T.get_account("consensus-dest")
        amount = 5_000_000_000

        tx = create_account_tx(app, dest, amount)
        assert app.herder.recv_transaction(tx) == TX_STATUS_PENDING
        assert clock.crank_until(lambda: load_or_none(app, dest) is not None, 60)
        assert load_or_none(app, dest).get_balance() == amount

    def test_recv_transaction_statuses(self, clock):
        """HerderTests.cpp:158-214 ("recvTx")."""
        app = make_scp_app(clock)
        app.herder.bootstrap()
        dest = T.get_account("tx-status-dest")

        tx = create_account_tx(app, dest, 10_000_000_000)
        assert app.herder.recv_transaction(tx) == TX_STATUS_PENDING
        assert app.herder.recv_transaction(tx) == TX_STATUS_DUPLICATE

        # bad sequence number
        root = T.root_key_for(app)
        bad = T.tx_from_ops(
            app, root, 999999, [T.create_account_op(dest, 10_000_000_000)]
        )
        assert app.herder.recv_transaction(bad) == TX_STATUS_ERROR

    def test_externalized_txs_removed_from_queue(self, clock):
        app = make_scp_app(clock)
        app.herder.bootstrap()
        dest = T.get_account("queue-dest")
        tx = create_account_tx(app, dest, 10_000_000_000)
        assert app.herder.recv_transaction(tx) == TX_STATUS_PENDING
        assert clock.crank_until(lambda: load_or_none(app, dest) is not None, 60)
        for gen in app.herder.received_transactions:
            assert not gen

    def test_scp_state_persists_and_restores(self, clock):
        app = make_scp_app(clock)
        app.herder.bootstrap()
        lm = app.ledger_manager
        assert clock.crank_until(lambda: lm.get_last_closed_ledger_num() >= 2, 30)

        from stellar_tpu.main.persistentstate import K_LAST_SCP_DATA

        blob = app.persistent_state.get_state(K_LAST_SCP_DATA)
        assert blob  # persisted on emit

        # a fresh herder over the same database restores latest SCP messages
        herder2 = Herder(app)
        herder2.restore_scp_state()
        assert any(
            herder2.scp.get_current_state(seq)
            for seq in range(2, lm.get_last_closed_ledger_num() + 2)
        )


class TestTxQueueAging:
    def test_four_generation_shift(self, clock):
        app = make_scp_app(clock)
        app.herder.bootstrap()
        h = app.herder
        root = T.root_key_for(app)
        dest = T.get_account("aging-dest")
        tx = T.tx_from_ops(
            app, root, root_seq(app) + 1, [T.create_account_op(dest, 10_000_000_000)]
        )
        from stellar_tpu.herder.herder import TxMap

        acc = tx.get_source_id().value
        h.received_transactions[0].setdefault(acc, TxMap()).add_tx(tx)
        for expected_gen in (1, 2, 3):
            h._age_pending_transactions()
            assert acc in h.received_transactions[expected_gen]
        # oldest generation accumulates, never drops
        h._age_pending_transactions()
        assert acc in h.received_transactions[3]

    def test_gap_seq_tx_trimmed_at_proposal(self, clock):
        """A tx with an unreachable sequence number is trimmed from the
        proposed set and dropped from the queue (HerderImpl.cpp trimInvalid +
        removeReceivedTxs)."""
        app = make_scp_app(clock)
        app.herder.bootstrap()
        h = app.herder
        root = T.root_key_for(app)
        dest = T.get_account("gap-dest")
        tx = T.tx_from_ops(
            app, root, root_seq(app) + 10, [T.create_account_op(dest, 10_000_000_000)]
        )
        from stellar_tpu.herder.herder import TxMap

        acc = tx.get_source_id().value
        h.received_transactions[0].setdefault(acc, TxMap()).add_tx(tx)
        lm = app.ledger_manager
        start = lm.get_last_closed_ledger_num()
        assert clock.crank_until(lambda: lm.get_last_closed_ledger_num() > start, 30)
        for gen in h.received_transactions:
            assert acc not in gen
        assert load_or_none(app, dest) is None


class TestPendingWait:
    """``tx_queue`` ``closed`` / ``pending_wait_s`` / ``pending_wait_max_s``
    (PR 51): a transaction is stamped where ``recv_transaction`` answers
    PENDING, on the tracer's clock (the virtual one here), and its wait is
    added where its externalized set leaves the queue."""

    @pytest.mark.parametrize(
        "case", ["closed", "closed-untraced", "two-arrivals", "trimmed", "never-pending"]
    )
    def test_wait_from_admission_to_the_closed_ledger(self, clock, case):
        from stellar_tpu.herder.herder import TxMap

        cfg = T.get_test_config(0)
        cfg.MANUAL_CLOSE = False
        cfg.TRACE_ENABLED = case != "closed-untraced"
        app = Application(clock, cfg, new_db=True)
        app.herder = h = Herder(app)
        h.bootstrap()
        lm = app.ledger_manager
        # the first ledgers close empty: they add nothing
        assert clock.crank_until(lambda: lm.get_last_closed_ledger_num() >= 2, 30)
        s0 = h.tx_queue_stats()
        assert (s0["closed"], s0["pending_wait_s"], s0["pending_wait_max_s"]) == (0, 0.0, 0.0)
        hist = app.metrics.new_histogram(("herder", "tx", "pending-wait"))
        assert hist.count == 0

        root = T.root_key_for(app)
        seq = root_seq(app)
        dests = [T.get_account("wait-%d" % i) for i in range(2)]
        txs = [
            T.tx_from_ops(app, root, seq + 1 + i, [T.create_account_op(d, 10_000_000_000)])
            for i, d in enumerate(dests)
        ]
        closed_at = []
        externalized = h.value_externalized

        def spy(slot, value):
            externalized(slot, value)
            closed_at.append(clock.now())

        h.value_externalized = spy
        start = lm.get_last_closed_ledger_num()
        arrivals = []

        def admit(tx):
            arrivals.append(clock.now())
            assert h.recv_transaction(tx) == TX_STATUS_PENDING

        if case == "two-arrivals":
            admit(txs[0])
            clock.set_current_virtual_time(clock.now() + 0.25)
            admit(txs[1])
        elif case == "trimmed":
            # the second no longer follows once the first is gone: the
            # trigger's trim removes it, and it never reaches a ledger
            admit(txs[0])
            admit(txs[1])
            h._remove_received_txs([txs[0]])
        elif case == "never-pending":
            # a transaction this node first sees inside the set that
            # externalizes: in the queue's maps, never stamped
            acc = txs[0].get_source_id().value
            h.received_transactions[0].setdefault(acc, TxMap()).add_tx(txs[0])
        else:
            admit(txs[0])
        assert clock.crank_until(lambda: lm.get_last_closed_ledger_num() > start, 30)
        s = h.tx_queue_stats()
        assert s["pending"] == 0

        if case == "trimmed":
            assert s["trimmed"] == 1 and load_or_none(app, dests[1]) is None
            want_closed, waits = 0, []
        elif case == "never-pending":
            assert load_or_none(app, dests[0]) is not None
            want_closed, waits = 1, []
        else:
            n = len(arrivals)
            assert all(load_or_none(app, d) is not None for d in dests[:n])
            want_closed, waits = n, [closed_at[0] - at for at in arrivals]
            # a ledger's wait on this network: whole virtual seconds
            assert waits[0] >= 1.0
        assert s["closed"] == want_closed
        assert s["pending_wait_s"] == sum(waits)
        assert s["pending_wait_max_s"] == max(waits, default=0.0)
        assert hist.count == len(waits)
        if waits:
            assert hist.max_value == max(waits) * 1000.0
        # /info carries the block as the herder gives it
        assert set(s0) == set(s) >= {"closed", "pending_wait_s", "pending_wait_max_s", "trimmed"}
        assert bool(app.tracer.spans()) == cfg.TRACE_ENABLED


class TestTxSetValidity:
    """Ported from the reference's 'txset' case (HerderTests.cpp:162-316):
    one funded source account, 2 destination chains x 5 txs; each section
    perturbs the set, asserts check_valid flips false, and trim_invalid
    restores validity."""

    def _world(self, clock):
        from stellar_tpu.herder.txset import TxSetFrame
        from stellar_tpu.ledger.accountframe import AccountFrame

        cfg = T.get_test_config(75)
        cfg.MANUAL_CLOSE = True
        app = Application.create(clock, cfg, new_db=True)
        app.start()
        lm = app.ledger_manager
        root = T.root_key_for(app)
        n_accounts, n_txs = 2, 5
        payment = lm.get_min_balance(0)
        source = T.get_account("source")
        fund = n_accounts * n_txs * lm.get_tx_fee() + payment
        root_seq = AccountFrame.load_account(
            root.get_public_key(), app.database
        ).get_seq_num()
        T.apply_tx(
            app,
            T.tx_from_ops(
                app, root, root_seq + 1, [T.create_account_op(source, fund)]
            ),
        )
        seq = AccountFrame.load_account(
            source.get_public_key(), app.database
        ).get_seq_num()
        txs = []
        for i in range(n_accounts):
            dest = T.get_account(f"A{i}")
            for j in range(n_txs):
                seq += 1
                op = (
                    T.create_account_op(dest, payment)
                    if j == 0
                    else T.payment_op(dest, payment)
                )
                txs.append(T.tx_from_ops(app, source, seq, [op]))
        ts = TxSetFrame(lm.last_closed.hash, txs)
        return app, ts, source, seq, payment

    def _check_trim_restores(self, app, ts):
        assert not ts.check_valid(app)
        ts.trim_invalid(app)
        assert ts.check_valid(app)

    def test_success_and_trim_noop(self, clock):
        app, ts, *_ = self._world(clock)
        ts.sort_for_hash()
        assert ts.check_valid(app)
        assert ts.trim_invalid(app) == []
        assert ts.check_valid(app)
        app.graceful_stop()

    def test_out_of_hash_order(self, clock):
        app, ts, *_ = self._world(clock)
        ts.sort_for_hash()
        ts.transactions[0], ts.transactions[1] = (
            ts.transactions[1],
            ts.transactions[0],
        )
        assert not ts.check_valid(app)
        ts.sort_for_hash()
        assert ts.check_valid(app)
        app.graceful_stop()

    def test_no_user(self, clock):
        """A tx from a nonexistent account invalidates the set; trim fixes."""
        app, ts, *_ = self._world(clock)
        ghost = T.get_account("ghost")
        ts.add_transaction(
            T.tx_from_ops(app, ghost, (2 << 32) + 1, [T.payment_op(ghost, 1)])
        )
        ts.sort_for_hash()
        self._check_trim_restores(app, ts)
        app.graceful_stop()

    @pytest.mark.parametrize("where", ["begin", "middle", "after"])
    def test_sequence_gap(self, clock, where):
        app, ts, source, seq, payment = self._world(clock)
        if where == "after":
            ts.add_transaction(
                T.tx_from_ops(
                    app, source, seq + 5, [T.payment_op(source, payment)]
                )
            )
        else:
            # drop one tx of the source's chain to open a gap
            drop = 0 if where == "begin" else 3
            chain = sorted(ts.transactions, key=lambda t: t.get_seq_num())
            ts.remove_tx(chain[drop])
        ts.sort_for_hash()
        self._check_trim_restores(app, ts)
        app.graceful_stop()

    def test_insufficient_balance(self, clock):
        """One extra tx pushes the source below reserve for the whole set:
        the reference drops the entire account group."""
        app, ts, source, seq, payment = self._world(clock)
        ts.add_transaction(
            T.tx_from_ops(
                app, source, seq + 1, [T.payment_op(source, payment)]
            )
        )
        ts.sort_for_hash()
        self._check_trim_restores(app, ts)
        app.graceful_stop()


def count_chain_walks(monkeypatch):
    """Count ``TxSetFrame._check_account_chain`` calls (one an account a
    full pass); returns the list the calls land in."""
    from stellar_tpu.herder.txset import TxSetFrame

    walks = []
    inner = TxSetFrame._check_account_chain

    def counted(app, txs):
        walks.append(len(txs))
        return inner(app, txs)

    monkeypatch.setattr(TxSetFrame, "_check_account_chain", staticmethod(counted))
    return walks


class TestTxSetVerdictMemo:
    """A set found valid remembers the node and the last closed ledger it
    was found valid on: the next ``check_valid`` / ``trim_invalid`` there
    is the free gates and a comparison.  Anything that could change the
    answer — the set, the ledger, the node — costs the full pass again, and
    only ``True`` is ever remembered."""

    _world = TestTxSetValidity._world

    def _valid(self, clock):
        app, ts, source, seq, payment = self._world(clock)
        ts.sort_for_hash()
        assert ts.check_valid(app)
        assert app.ledger_manager.txset_validations == {"full": 1, "memo": 0, "trim_memo": 0}
        return app, ts, source, seq, payment

    def test_second_check_is_a_comparison(self, clock, monkeypatch):
        app, ts, *_ = self._valid(clock)
        results = [tx.result for tx in ts.transactions]
        walks = count_chain_walks(monkeypatch)
        app.tracer.clear()
        for _ in range(3):
            assert ts.check_valid(app)
        assert walks == []
        assert app.ledger_manager.txset_validations == {"full": 1, "memo": 3, "trim_memo": 0}
        # the span stays, marked; the frames keep the pass's results
        spans = [s for s in app.tracer.spans() if s.name == "txset.validate"]
        assert [s.attrs.get("memo") for s in spans] == [1, 1, 1]
        assert [tx.result for tx in ts.transactions] == results
        assert all(tx.get_result_code().name == "txSUCCESS" for tx in ts.transactions)
        info = app.command_handler.execute("info")["info"]
        assert info["txset_validations"] == {"full": 1, "memo": 3, "trim_memo": 0}
        app.graceful_stop()

    def test_trim_after_valid_check_walks_nothing(self, clock, monkeypatch):
        app, ts, *_ = self._valid(clock)
        walks = count_chain_walks(monkeypatch)
        before = list(ts.transactions)
        assert ts.trim_invalid(app) == []
        assert walks == [] and ts.transactions == before
        assert app.ledger_manager.txset_validations == {"full": 1, "memo": 0, "trim_memo": 1}
        app.graceful_stop()

    def test_full_trim_that_removed_nothing_is_remembered(self, clock, monkeypatch):
        app, ts, *_ = self._world(clock)
        assert ts.trim_invalid(app) == []
        walks = count_chain_walks(monkeypatch)
        assert ts.check_valid(app) and ts.trim_invalid(app) == []
        assert walks == []
        assert app.ledger_manager.txset_validations == {"full": 1, "memo": 1, "trim_memo": 1}
        app.graceful_stop()

    def test_trim_that_removed_something_leaves_no_memo(self, clock, monkeypatch):
        app, ts, source, seq, payment = self._world(clock)
        ts.add_transaction(T.tx_from_ops(app, source, seq + 5, [T.payment_op(source, payment)]))
        assert len(ts.trim_invalid(app)) == 1
        assert ts._valid_on is None
        walks = count_chain_walks(monkeypatch)
        assert ts.check_valid(app)
        assert walks == [10]  # one source account, its ten transactions
        assert app.ledger_manager.txset_validations["full"] == 2
        app.graceful_stop()

    @pytest.mark.parametrize("how", ["add_transaction", "remove_tx", "reorder_then_sort"])
    def test_a_changed_set_is_walked_again(self, clock, monkeypatch, how):
        app, ts, source, seq, payment = self._valid(clock)
        walks = count_chain_walks(monkeypatch)
        if how == "add_transaction":
            ts.add_transaction(T.tx_from_ops(app, source, seq + 1, [T.payment_op(source, 1)]))
            ts.sort_for_hash()
            want = False  # the eleventh payment takes the source below its reserve
        elif how == "remove_tx":
            ts.remove_tx(max(ts.transactions, key=lambda t: t.get_seq_num()))
            want = True
        else:
            ts.transactions[0], ts.transactions[1] = ts.transactions[1], ts.transactions[0]
            assert not ts.check_valid(app) and walks == []  # the order gate, before the memo
            ts.sort_for_hash()
            want = True
        assert ts._valid_on is None
        assert ts.check_valid(app) is want
        assert len(walks) == 1
        app.graceful_stop()

    def test_sort_that_moves_nothing_keeps_the_memo(self, clock, monkeypatch):
        app, ts, *_ = self._valid(clock)
        walks = count_chain_walks(monkeypatch)
        ts.sort_for_hash()
        ts.get_contents_hash()
        ts.to_xdr()
        assert ts.check_valid(app) and walks == []
        app.graceful_stop()

    def test_a_close_ends_the_memo(self, clock, monkeypatch):
        """After a close the set's ``previous_ledger_hash`` is not the last
        closed hash: invalid at the first gate, memo or not.  The same
        frames under the new hash are a new set and a full pass."""
        from stellar_tpu.herder.txset import TxSetFrame

        app, ts, *_ = self._valid(clock)
        lm = app.ledger_manager
        T.close_ledger_on(app, lm.last_closed.header.scpValue.closeTime + 5)
        walks = count_chain_walks(monkeypatch)
        assert not ts.check_valid(app) and walks == []
        assert lm.txset_validations["memo"] == 0
        # a caller that re-aims the frame in place still gets a full pass
        ts.previous_ledger_hash = lm.last_closed.hash
        assert ts.check_valid(app) and len(walks) == 1
        again = TxSetFrame(lm.last_closed.hash, ts.transactions)
        assert again.check_valid(app) and len(walks) == 2
        app.graceful_stop()

    def test_a_second_node_does_its_own_pass(self, clock, monkeypatch):
        """Two nodes of one process on the same last closed hash (a
        ``Simulation``): the verdict is the first node's alone."""
        app, ts, *_ = self._valid(clock)
        other, ts2, *_ = self._world(clock)
        assert other.ledger_manager.last_closed.hash == app.ledger_manager.last_closed.hash
        assert [t.get_full_hash() for t in sorted(ts2.transactions, key=lambda t: t.get_full_hash())] == [
            t.get_full_hash() for t in ts.transactions
        ]
        walks = count_chain_walks(monkeypatch)
        assert ts.check_valid(other) and len(walks) == 1
        assert other.ledger_manager.txset_validations == {"full": 1, "memo": 0, "trim_memo": 0}
        # ... and the verdict is now the second node's: the first walks again
        assert ts.check_valid(app) and len(walks) == 2
        assert ts.check_valid(app) and len(walks) == 2
        other.graceful_stop()
        app.graceful_stop()

    @pytest.mark.parametrize("via", ["check_valid", "trim_then_check"])
    def test_an_invalid_set_is_walked_and_metered_every_time(self, clock, monkeypatch, via):
        app, ts, source, seq, payment = self._world(clock)
        gap = T.tx_from_ops(app, source, seq + 5, [T.payment_op(source, payment)])
        ts.add_transaction(gap)
        ts.sort_for_hash()
        meter = app.metrics.new_meter(("transaction", "invalid", "bad-seq"), "transaction")
        walks = count_chain_walks(monkeypatch)
        for n in (1, 2, 3):
            assert not ts.check_valid(app)
            assert (len(walks), meter.count) == (n, n)
            assert gap.get_result_code().name == "txBAD_SEQ" and ts._valid_on is None
        assert app.ledger_manager.txset_validations == {"full": 3, "memo": 0, "trim_memo": 0}
        if via == "trim_then_check":
            assert ts.trim_invalid(app) == [gap] and meter.count == 4
            assert ts.check_valid(app) and len(walks) == 5
        app.graceful_stop()


class TestTriggeredLedgerValidatesOnce:
    """One node, ``MANUAL_CLOSE``, a pending backlog of twice the set limit:
    the triggered ledger is trimmed over the backlog, checked in full once
    after the surge filter, and every later question about the set (SCP's
    ``validate_value`` at nomination and each ballot step, the re-trim in
    ``combine_candidates``) is answered from that verdict.  The ledger that
    closes is the one that closes with every pass walked."""

    LIMIT = 5

    def _close_one(self, instance):
        clock = VirtualClock(VIRTUAL_TIME)
        cfg = T.get_test_config(instance)
        cfg.MANUAL_CLOSE = True
        app = Application.create(clock, cfg, new_db=True)
        try:
            app.start()
            lm = app.ledger_manager
            lm.current.header.maxTxSetSize = self.LIMIT
            root = T.root_key_for(app)
            seq = root_seq(app)
            keys = [T.get_account(f"backlog-{i}") for i in range(2 * self.LIMIT)]
            for i, k in enumerate(keys):
                T.apply_tx(app, T.tx_from_ops(app, root, seq + 1 + i, [T.create_account_op(k, 10**9)]))
            for i, k in enumerate(keys):
                k_seq = AccountFrame.load_account(k.get_public_key(), app.database).get_seq_num()
                tx = T.tx_from_ops(app, k, k_seq + 1, [T.payment_op(keys[i ^ 1], 1000 + i)])
                assert app.herder.recv_transaction(tx) == TX_STATUS_PENDING
            before = dict(lm.txset_validations)
            start = lm.get_last_closed_ledger_num()
            app.herder.trigger_next_ledger(lm.get_ledger_num())
            assert clock.crank_until(lambda: lm.get_last_closed_ledger_num() > start, 30)
            did = {k: v - before[k] for k, v in lm.txset_validations.items()}
            applied = app.metrics.new_meter(("ledger", "transaction", "count"), "tx").count
            return did, applied, lm.last_closed.hash, app.herder.num_pending_txs()
        finally:
            app.graceful_stop()
            clock.shutdown()

    def test_one_full_check_a_ledger_and_the_same_ledger(self, monkeypatch):
        from stellar_tpu.herder.txset import TxSetFrame

        did, applied, closed_hash, pending = self._close_one(77)
        assert did["full"] <= 2 and did["memo"] + did["trim_memo"] >= 7, did
        assert did["trim_memo"] >= 1
        assert (applied, pending) == (self.LIMIT, self.LIMIT)

        # the memo defeated: every pass walks, as before it existed
        monkeypatch.setattr(TxSetFrame, "_found_valid", lambda self, lm, lcl: False)
        walked, applied2, walked_hash, pending2 = self._close_one(77)
        assert walked["memo"] == walked["trim_memo"] == 0
        assert walked["full"] == did["full"] + did["memo"] + did["trim_memo"]
        assert (applied2, pending2) == (applied, pending)
        assert walked_hash == closed_hash


class TestSurgePricing:
    """Ported from the reference's 'surge' case (HerderTests.cpp:320-490):
    DESIRED_MAX_TX_PER_LEDGER=5, competing accounts, the filter keeps the
    5 best-paying txs and the result stays valid."""

    def _world(self, clock):
        from stellar_tpu.herder.txset import TxSetFrame

        cfg = T.get_test_config(76)
        cfg.MANUAL_CLOSE = True
        app = Application.create(clock, cfg, new_db=True)
        app.start()
        # the filter reads the current header's maxTxSetSize directly
        app.ledger_manager.current.header.maxTxSetSize = 5
        root = T.root_key_for(app)
        root_seq = AccountFrame.load_account(
            root.get_public_key(), app.database
        ).get_seq_num()
        dest = T.get_account("destAccount")
        accs = {}
        for name in ("accountB", "accountC"):
            accs[name] = T.get_account(name)
            root_seq += 1
            T.apply_tx(
                app,
                T.tx_from_ops(
                    app,
                    root,
                    root_seq,
                    [T.create_account_op(accs[name], 5_000_000_000)],
                ),
            )
        seqs = {
            "root": root_seq,
            "accountB": AccountFrame.load_account(
                accs["accountB"].get_public_key(), app.database
            ).get_seq_num(),
            "accountC": AccountFrame.load_account(
                accs["accountC"].get_public_key(), app.database
            ).get_seq_num(),
        }
        keys = {"root": root, **accs}
        ts = TxSetFrame(app.ledger_manager.last_closed.hash, [])
        return app, ts, keys, seqs, dest

    def _pay(self, app, ts, keys, seqs, who, dest, amount, fee_mult=1):
        seqs[who] += 1
        fee = app.ledger_manager.get_tx_fee() * fee_mult
        ts.add_transaction(
            T.tx_from_ops(
                app, keys[who], seqs[who], [T.payment_op(dest, amount)],
                fee=fee,
            )
        )

    def test_over_surge(self, clock):
        app, ts, keys, seqs, dest = self._world(clock)
        for n in range(10):
            self._pay(app, ts, keys, seqs, "root", dest, n + 10)
        ts.sort_for_hash()
        ts.surge_pricing_filter(app.ledger_manager)
        assert len(ts.transactions) == 5
        assert ts.check_valid(app)
        app.graceful_stop()

    def test_over_surge_shuffled(self, clock):
        import random as _r

        app, ts, keys, seqs, dest = self._world(clock)
        for n in range(10):
            self._pay(app, ts, keys, seqs, "root", dest, n + 10)
        # filter the UNSORTED set: the result must not depend on input
        # order (sorting first would make this identical to test_over_surge)
        _r.Random(7).shuffle(ts.transactions)
        ts.surge_pricing_filter(app.ledger_manager)
        assert len(ts.transactions) == 5
        ts.sort_for_hash()
        assert ts.check_valid(app)
        app.graceful_stop()

    def test_one_account_paying_more(self, clock):
        app, ts, keys, seqs, dest = self._world(clock)
        for n in range(10):
            self._pay(app, ts, keys, seqs, "root", dest, n + 10)
            self._pay(app, ts, keys, seqs, "accountB", dest, n + 10, fee_mult=2)
        ts.sort_for_hash()
        ts.surge_pricing_filter(app.ledger_manager)
        assert len(ts.transactions) == 5
        assert ts.check_valid(app)
        b_key = keys["accountB"].get_public_key()
        assert all(tx.get_source_id() == b_key for tx in ts.transactions)
        app.graceful_stop()

    def test_one_account_paying_more_except_one_tx(self, clock):
        """accountB pays 3x except one tx at 1x: the account's fee RATIO is
        its minimum, so root (uniform 2x) wins the whole window."""
        app, ts, keys, seqs, dest = self._world(clock)
        for n in range(10):
            self._pay(app, ts, keys, seqs, "root", dest, n + 10, fee_mult=2)
            self._pay(
                app, ts, keys, seqs, "accountB", dest, n + 10,
                fee_mult=(3 if n != 1 else 1),
            )
        ts.sort_for_hash()
        ts.surge_pricing_filter(app.ledger_manager)
        assert len(ts.transactions) == 5
        assert ts.check_valid(app)
        root_key = keys["root"].get_public_key()
        assert all(tx.get_source_id() == root_key for tx in ts.transactions)
        app.graceful_stop()

    def test_a_lot_of_txs(self, clock):
        app, ts, keys, seqs, dest = self._world(clock)
        for n in range(30):
            for who in ("root", "accountB", "accountC"):
                self._pay(app, ts, keys, seqs, who, dest, n + 10)
        ts.sort_for_hash()
        ts.surge_pricing_filter(app.ledger_manager)
        assert len(ts.transactions) == 5
        assert ts.check_valid(app)
        app.graceful_stop()


class TestCombineCandidates:
    def test_composite_value_selection(self, clock):
        """HerderTests.cpp:507-560 — combineCandidates builds the composite
        StellarValue: max closeTime across candidates, biggest txset wins
        (a later candidate with a higher closeTime but smaller txset moves
        the closeTime without displacing the bigger set)."""
        from stellar_tpu.herder.txset import TxSetFrame
        from stellar_tpu.xdr.base import xdr_to_opaque
        from stellar_tpu.xdr.ledger import StellarValue

        app = make_scp_app(clock, 31)
        try:
            herder = app.herder
            lm = app.ledger_manager
            lcl = lm.last_closed
            root = T.root_key_for(app)
            a1 = T.get_account("combine-a1")
            candidates = set()

            def add_to_candidates(txset, close_time):
                txset.sort_for_hash()
                herder.recv_tx_set(txset.get_contents_hash(), txset)
                candidates.add(xdr_to_opaque(
                    StellarValue(txset.get_contents_hash(), close_time, [], 0)
                ))

            def txs(n):
                seq = root_seq(app)
                return [
                    T.tx_from_ops(app, root, seq + 1 + i,
                                  [T.create_account_op(a1, 10**7)])
                    for i in range(n)
                ]

            def combined():
                return StellarValue.from_xdr(
                    herder.combine_candidates(1, candidates)
                )

            txset0 = TxSetFrame(lcl.hash, [])
            txset0.sort_for_hash()
            add_to_candidates(txset0, 100)
            sv = combined()
            assert sv.closeTime == 100
            assert sv.txSetHash == txset0.get_contents_hash()

            txset1 = TxSetFrame(lcl.hash, txs(10))
            add_to_candidates(txset1, 10)
            sv = combined()
            assert sv.closeTime == 100  # max close time, not txset1's 10
            assert sv.txSetHash == txset1.get_contents_hash()  # biggest set

            txset2 = TxSetFrame(lcl.hash, txs(5))
            add_to_candidates(txset2, 1000)
            sv = combined()
            assert sv.closeTime == 1000  # new max close time...
            assert sv.txSetHash == txset1.get_contents_hash()  # ...same set
        finally:
            app.database.close()
