"""The node's schedule for the collector's full passes (`util/collector.py`).

While an `Application` from `Application.create` lives, generation 2 never
fires on an allocation count: a full pass runs at a ledger boundary (the tail
of `LedgerManager.close_ledger`) or from the overlay's tick, when the due
rule says so.  Real closes of a standalone node; passes are counted by a
`gc.callbacks` entry of the test's own."""

import gc
import itertools
import weakref

import pytest
from test_serial_apply import close, node, pay

import stellar_tpu.xdr as X
from stellar_tpu.ledger.accountframe import AccountFrame
from stellar_tpu.tx import testutils as T
from stellar_tpu.tx.frame import TransactionFrame
from stellar_tpu.util import collector

WIDE = 700  # transactions a close: ~3.5 young passes of the collector each


@pytest.fixture(autouse=True)
def no_holder_left_over():
    """Tests before this file may have dropped an `Application` without
    stopping it: start from the interpreter's own schedule."""
    for tracer in list(collector._holders):
        collector.release(tracer)
    before = gc.get_threshold()
    assert before[2] != collector._NEVER
    yield
    assert not collector.held()
    assert gc.get_threshold() == before


class Passes:
    """Every generation-2 pass from here on: [(cause the node gave, the
    spans open on the node's tracer)]."""

    def __init__(self, app):
        self.app = app
        self.seen = []

    def __call__(self, phase, info):
        if phase == "start" and info["generation"] == 2:
            stack = self.app.tracer._stack()
            self.seen.append((collector._cause, [s.name for s in stack if s.name != "gc.full"]))

    def __enter__(self):
        gc.callbacks.append(self)
        return self.seen

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


class Wide:
    """A node with `WIDE` funded accounts and the set size to close them."""

    def __init__(self, instance):
        from stellar_tpu.herder.ledgerclose import LedgerCloseData
        from stellar_tpu.herder.txset import TxSetFrame
        from stellar_tpu.xdr.base import xdr_to_opaque
        from stellar_tpu.xdr.ledger import LedgerUpgrade, LedgerUpgradeType, StellarValue

        self.app, self.clock = node(instance)
        self.lm = lm = self.app.ledger_manager
        self.keys = [T.get_account("gc-%d" % i) for i in range(WIDE)]
        root = T.root_key_for(self.app)
        seq = AccountFrame.load_account(root.get_public_key(), self.app.database).get_seq_num()
        txs = [
            T.tx_from_ops(self.app, root, seq + 1 + j, [T.create_account_op(k, 10**9) for k in self.keys[i : i + 100]])
            for j, i in enumerate(range(0, WIDE, 100))
        ]
        txset = TxSetFrame(lm.last_closed.hash, txs)
        txset.sort_for_hash()
        upgrade = xdr_to_opaque(LedgerUpgrade(LedgerUpgradeType.LEDGER_UPGRADE_MAX_TX_SET_SIZE, WIDE))
        value = StellarValue(txset.get_contents_hash(), lm.last_closed.header.scpValue.closeTime + 5, [upgrade], 0)
        lm.close_ledger(LedgerCloseData(lm.current.header.ledgerSeq, txset, value))
        self.first = lm.last_closed.header.ledgerSeq << 32
        self.round = 0

    def payments(self):
        self.round += 1
        return [
            pay(self.app, k, self.first + self.round, self.keys[i ^ 1], 100)
            for i, k in enumerate(self.keys)
        ]

    def validate_and_close(self, txs, through="externalize_value"):
        """As a validator does with a peer's set (``through`` the close
        pipeline, or ``close_ledger`` alone)."""
        from stellar_tpu.herder.ledgerclose import LedgerCloseData
        from stellar_tpu.herder.txset import TxSetFrame
        from stellar_tpu.xdr.ledger import StellarValue

        lm = self.lm
        txset = TxSetFrame(lm.last_closed.hash, txs)
        txset.sort_for_hash()
        value = StellarValue(txset.get_contents_hash(), lm.last_closed.header.scpValue.closeTime + 5, [], 0)
        assert txset.check_valid(self.app)
        getattr(lm, through)(LedgerCloseData(lm.current.header.ledgerSeq, txset, value))
        assert all(tx.get_result_code().name == "txSUCCESS" for tx in txs)

    def stop(self):
        self.app.graceful_stop()
        self.clock.shutdown()


@pytest.fixture
def wide():
    w = Wide(231)
    try:
        yield w
    finally:
        w.stop()


# -- taken with the node, given back with it ------------------------------------


def test_taken_at_create_and_given_back_at_graceful_stop():
    before = gc.get_threshold()
    app, clock = node(230)
    try:
        assert collector.held()
        assert gc.get_threshold() == (before[0], before[1], collector._NEVER)
        assert collector._on_pass in gc.callbacks
    finally:
        app.graceful_stop()
        clock.shutdown()
    assert not collector.held()
    assert gc.get_threshold() == before
    assert collector._on_pass not in gc.callbacks
    collector.release(app.tracer)  # a second release is a no-op
    assert gc.get_threshold() == before


@pytest.mark.parametrize("order", list(itertools.permutations(range(3))))
def test_three_applications_stopped_in_any_order(order):
    before = gc.get_threshold()
    nodes = [node(232 + i) for i in range(3)]
    try:
        for n, i in enumerate(order):
            assert gc.get_threshold()[2] == collector._NEVER, n
            nodes[i][0].graceful_stop()
        assert gc.get_threshold() == before
    finally:
        for _app, clock in nodes:
            clock.shutdown()


def test_an_application_that_is_not_a_node_takes_nothing():
    """`Application(...)` alone (offline utilities) is not `create`."""
    from stellar_tpu.main.application import Application
    from stellar_tpu.util.clock import VIRTUAL_TIME, VirtualClock

    before = gc.get_threshold()
    clock = VirtualClock(VIRTUAL_TIME)
    app = Application(clock, T.get_test_config(235), new_db=True)
    try:
        assert not collector.held()
        assert gc.get_threshold() == before
        checks = collector.stats()["boundary_checks"]
        close(app, [])
        assert collector.stats()["boundary_checks"] == checks
    finally:
        app.graceful_stop()
        clock.shutdown()


# -- where the passes run --------------------------------------------------------


def test_no_full_pass_inside_a_wide_close_and_the_boundary_pass_when_due(wide, monkeypatch):
    verdicts = []
    real_due = collector._due

    def due():
        verdicts.append((real_due(), gc.get_count()[2], collector.stats()["closes_since_full"]))
        return verdicts[-1][0]

    monkeypatch.setattr(collector, "_due", due)
    gc.collect()
    with Passes(wide.app) as seen:
        for _ in range(2 * collector.CLOSES_DUE):
            wide.validate_and_close(wide.payments())
    # every pass ran at the boundary: inside ledger.close, after the commit
    # (close.commit has ended), none from check_valid to the commit's end
    assert seen and all(p == ("boundary", ["ledger.close"]) for p in seen), seen
    # and it ran when the rule said due and not otherwise
    assert [v[0] for v in verdicts].count(True) == len(seen)
    for was_due, young, closes in verdicts:
        assert was_due == (
            young >= collector.YOUNG_PASSES_DUE or (young > 10 and closes >= collector.CLOSES_DUE)
        )
    # a WIDE close crosses the interpreter's own count within CLOSES_DUE
    # closes, so the closes decide: one pass every CLOSES_DUE boundaries
    assert [v[0] for v in verdicts] == ([False] * (collector.CLOSES_DUE - 1) + [True]) * 2
    stats = collector.stats()
    assert stats["closes_since_full"] == 0


def test_the_interpreter_would_have_passed_inside_the_same_close(wide):
    """The control: with the policy given back, the same closes carry full
    passes in the middle."""
    collector.release(wide.app.tracer)
    # as the benchmark's harness opens its window: what lives is set aside,
    # so the interpreter's 25 % rule holds no pass back
    gc.freeze()
    gc.collect()
    try:
        with Passes(wide.app) as seen:
            for _ in range(2 * collector.CLOSES_DUE):
                wide.validate_and_close(wide.payments())
    finally:
        gc.unfreeze()
    assert seen
    assert all(cause == "explicit" for cause, _ in seen)
    assert any(stack != ["ledger.close"] for _, stack in seen), seen


def test_a_closed_set_dies_with_its_last_holder_and_a_cycle_within_the_bound(wide):
    """A set's frames are not cyclic garbage: they die when the caller lets
    go, with no pass.  What is cyclic and dropped during close n is dead by
    the boundary of close n + CLOSES_DUE."""

    class Knot:
        pass

    gc.collect()
    passes = collector.stats()["full_passes"]
    txs = wide.payments()
    frame = weakref.ref(txs[7])
    operation = weakref.ref(txs[7].operations[0])
    a, b = Knot(), Knot()
    a.other, b.other = b, a
    knot = weakref.ref(a)
    wide.validate_and_close(txs)
    del txs, a, b
    assert frame() is None and operation() is None
    assert collector.stats()["full_passes"] == passes
    assert knot() is not None
    for _ in range(collector.CLOSES_DUE):
        assert knot() is not None or collector.stats()["full_passes"] > passes
        wide.validate_and_close(wide.payments())
    assert knot() is None


def test_an_operation_frame_does_not_keep_its_transaction():
    app, clock = node(237)
    try:
        tx = pay(app, T.get_account("gc-a"), 1, T.get_account("gc-b"), 1)
        op = tx.operations[0]
        assert op.parent_tx is tx
        assert op.get_source_id() == tx.envelope.tx.sourceAccount
        again = TransactionFrame(app.network_id, X.TransactionEnvelope.from_xdr(tx.envelope.to_xdr()))
        assert again.operations[0].parent_tx is again
        dead = weakref.ref(again)
        del again
        assert dead() is None
    finally:
        app.graceful_stop()
        clock.shutdown()


def test_a_replaced_cache_line_dies_with_its_last_reader(wide):
    """The entry cache memoizes a readonly frame on a line's entry, which
    points back at the entry: a line a close replaces must not leave as a
    cycle."""
    db = wide.app.database
    source = wide.keys[0].get_public_key()
    AccountFrame.load_account(source, db, readonly=True)
    line = AccountFrame.load_account(source, db, readonly=True)
    assert AccountFrame.load_account(source, db, readonly=True) is line  # memoized
    entry, frame = weakref.ref(line.entry), weakref.ref(line)
    del line
    assert entry() is not None
    gc.collect()
    passes = collector.stats()["full_passes"]
    wide.validate_and_close(wide.payments())
    assert entry() is None and frame() is None
    assert collector.stats()["full_passes"] == passes


def test_a_close_that_raises_leaves_the_policy_and_later_closes_collect(wide, monkeypatch):
    held = gc.get_threshold()
    real = wide.lm._apply_transactions
    calls = []

    def apply_transactions(txs, delta, results):
        calls.append(len(txs))
        if len(calls) == 1:
            raise RuntimeError("a fault in the apply loop")
        return real(txs, delta, results)

    monkeypatch.setattr(wide.lm, "_apply_transactions", apply_transactions)
    txs = wide.payments()
    before = wide.lm.last_closed.hash
    with pytest.raises(RuntimeError, match="a fault in the apply loop"):
        wide.validate_and_close(txs, through="close_ledger")
    assert wide.lm.last_closed.hash == before
    assert collector.held() and gc.get_threshold() == held and held[2] == collector._NEVER
    gc.collect()
    with Passes(wide.app) as seen:
        wide.validate_and_close(txs, through="close_ledger")  # the same set closes on the retry
        for _ in range(collector.CLOSES_DUE - 1):
            wide.validate_and_close(wide.payments())
    assert seen == [("boundary", ["ledger.close"])]


def test_the_idle_check_collects_with_no_close(wide):
    """From the overlay's tick: a node that closes nothing still collects
    once the young passes since the last full pass say so."""

    class Knot:
        pass

    gc.collect()
    a, b = Knot(), Knot()
    a.other, b.other = b, a
    knot = weakref.ref(a)
    gc.collect(1)  # grown old: no young pass will look at it again
    del a, b
    om = wide.app.overlay_manager
    checks = collector.stats()["boundary_checks"]
    with Passes(wide.app) as seen:
        om.tick()
        assert seen == [] and knot() is not None  # nothing due yet
        # what a flooded node holds: containers enough for the young passes
        keep = [[] for _ in range(8000 * (collector.YOUNG_PASSES_DUE + 10))]
        assert gc.get_count()[2] >= collector.YOUNG_PASSES_DUE
        assert knot() is not None
        om.tick()
        om.tick_timer.cancel()
    assert seen == [("timer", [])]
    assert knot() is None
    assert len(keep) and collector.stats()["boundary_checks"] == checks
    (span,) = [s for s in wide.app.tracer.spans() if s.name == "gc.full" and s.attrs["cause"] == "timer"]
    assert span.parent is None and span.attrs["collected"] >= 2


# -- the same ledgers -----------------------------------------------------------


def test_ten_ledgers_hash_the_same_as_the_plain_nodes():
    """The node with the policy, then the plain one (the interpreter's own
    schedule) fed the same sets: every ledger hash, and the accounts."""
    from test_serial_apply import accounts_of

    def run(plain, sets):
        w = Wide(238)
        if plain:
            collector.release(w.app.tracer)
        try:
            gc.collect()
            passes = collector.stats()["boundary_passes"]
            hashes = [w.lm.last_closed.hash]
            for i in range(10):
                if plain:
                    nid = w.app.network_id
                    txs = [TransactionFrame(nid, X.TransactionEnvelope.from_xdr(b)) for b in sets[i]]
                else:
                    txs = w.payments()
                    sets.append([tx.envelope.to_xdr() for tx in txs])
                w.validate_and_close(txs)
                hashes.append(w.lm.last_closed.hash)
            assert (collector.stats()["boundary_passes"] > passes) == (not plain)
            assert w.app.invariants.total_violations == 0
            return hashes, accounts_of(w.app)
        finally:
            w.stop()

    sets = []
    assert run(False, sets) == run(True, sets)
