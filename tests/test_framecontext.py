"""Close-scoped frame identity map (ledger/framecontext.py) and the
seal-on-store CoW snapshot plane (ledger/entryframe.py, round 9).

The FrameContext hands out ONE AccountFrame per touched account per close;
the reference loads a fresh frame per touch.  Seal-on-store shares the
storing frame's live entry with the delta/cache/store-buffer instead of
deep-copying per store.  The contract for BOTH planes is equivalence: a
node with the knob on must produce bit-identical ledgers, bit-identical
SQL state, AND bit-identical tx/fee history rows (including the per-op
LedgerEntryChanges metas) to one with it off — for payments, fee charging,
failed-tx rollbacks, same-close create+pay chains, signer mutations,
merges, offer crossings, and inflation.  The differential runner below is
therefore parametrized over the knob (FRAME_CONTEXT, COW_ENTRY_SNAPSHOTS)
and PARANOID_MODE audits every close on both sides, with the invariant
plane all-on (the "aliasing/copy-elision PRs land invariants-green"
landing policy, ROADMAP Correctness).

Mechanics tests below pin the map itself (identity, savepoint-lockstep
eviction, the readonly-shell store guard, the stale-context refusal) and
the seal contract (a sealed entry is never mutated in place — hostile
mutation attempts must transparently CoW, proven against the shared
snapshot's bytes)."""

import pytest

import stellar_tpu.xdr as X
from stellar_tpu.main.application import Application
from stellar_tpu.tx import testutils as T
from stellar_tpu.util import VIRTUAL_TIME, VirtualClock

RC = X.TransactionResultCode


@pytest.fixture
def clock():
    c = VirtualClock(VIRTUAL_TIME)
    yield c
    c.shutdown()


_dump_state = T.dump_state  # the shared bit-exactness oracle (testutils)


def plain_reference_config(cfg):
    """What benchmarks/reference.py's `replay_hashes` sets on the node it
    holds the chip's ledger hashes against: the host verifier and none of
    the planes a shipped node adds around the close."""
    cfg.SIGNATURE_BACKEND = "cpu"
    cfg.CLOSE_PIPELINE = False
    cfg.INGEST_BATCH = False
    cfg.BACKGROUND_BUCKET_MERGE = False
    cfg.INVARIANT_CHECKS = []


class _Runner:
    """Drive the same close sequence through two apps (`knob` on / off, or
    the shipped default / the plain reference node) and compare ledger
    hashes + SQL + history after every close."""

    KNOBS = {
        "frame_context": "FRAME_CONTEXT",
        "cow": "COW_ENTRY_SNAPSHOTS",
        "close_pipeline": "CLOSE_PIPELINE",
    }

    def __init__(self, clock, instance_base, knob="frame_context"):
        self.knob = knob
        self.apps = []
        for i, on in enumerate((True, False)):
            cfg = T.get_test_config(instance_base + i)
            if knob != "reference":
                setattr(cfg, self.KNOBS[knob], on)
            elif not on:
                plain_reference_config(cfg)
            # audit every close on both sides; the plain node audits nothing
            cfg.PARANOID_MODE = on or knob != "reference"
            self.apps.append(Application(clock, cfg, new_db=True))

    def close(self, build_txs):
        results = []
        for app in self.apps:
            lm = app.ledger_manager
            txs = build_txs(app, T.root_key_for(app))
            # the close_pipeline legs close via externalize_value so the
            # pipeline-on app routes through the scheduler's enqueue/
            # drain/join machinery (the consensus path), not the inline
            # close the off-knob app takes
            T.close_ledger_on(
                app, lm.last_closed.header.scpValue.closeTime + 5, txs,
                externalize=(self.knob == "close_pipeline"),
            )
            results.append([tx.get_result_code() for tx in txs])
        fc_app, ref_app = self.apps
        assert results[0] == results[1], "tx result codes diverged"
        assert (
            fc_app.ledger_manager.last_closed.hash
            == ref_app.ledger_manager.last_closed.hash
        ), "ledger hash diverged"
        assert _dump_state(fc_app.database) == _dump_state(
            ref_app.database
        ), "SQL state (entries or history metas) diverged"
        # the ledger-invariant plane (all-on by default in test configs)
        # audited both sides of every close above: FRAME_CONTEXT must stay
        # invariant-clean, not merely hash-identical to context-off
        for app in self.apps[: 1 if self.knob == "reference" else 2]:
            inv = app.invariants
            assert inv.total_violations == 0, inv.dump_info()
            assert inv.closes_checked > 0
            assert all(s["runs"] > 0 for s in inv.stats().values())
        if self.knob == "close_pipeline":
            # the scheduler must end every close drained and clean
            pipe = fc_app.close_pipeline
            assert pipe.queued_count() == 0
            assert pipe.n_quarantined == 0
        return results[0]

    def shutdown(self):
        for app in self.apps:
            app.database.close()


@pytest.fixture(params=["frame_context", "cow", "close_pipeline", "reference"])
def runner(clock, request):
    """Every differential scenario runs four times: FRAME_CONTEXT on/off,
    COW_ENTRY_SNAPSHOTS on/off, CLOSE_PIPELINE on/off (each vs an
    otherwise-default config), and the shipped default against the plain
    node benchmarks/reference.py builds — the equivalence that decides
    `ledger_hashes_differing` on the chip, here over offers, path payments,
    merges and inflation that no benchmark cell runs."""
    r = _Runner(
        clock,
        {
            "frame_context": 72,
            "cow": 84,
            "close_pipeline": 96,
            "reference": 108,
        }[request.param],
        knob=request.param,
    )
    yield r
    r.shutdown()


def _seq(app, sk):
    from stellar_tpu.ledger.accountframe import AccountFrame

    return AccountFrame.load_account(
        sk.get_public_key(), app.database
    ).get_seq_num() + 1


def test_differential_payments_fees_and_rollback(runner):
    """The benchmark shape plus a mid-close failed tx: the failed tx's
    frame mutations must unwind from the identity map in lockstep with
    the savepoint (its meta must also be byte-identical: empty)."""
    a, b = T.get_account("fc-a"), T.get_account("fc-b")
    runner.close(lambda app, root: [
        T.tx_from_ops(app, root, _seq(app, root), [
            T.create_account_op(a, 10**12), T.create_account_op(b, 10**12),
        ]),
    ])
    codes = runner.close(lambda app, root: [
        T.tx_from_ops(app, a, _seq(app, a), [T.payment_op(b, 10**7)]),
        T.tx_from_ops(app, b, _seq(app, b), [T.payment_op(a, 3 * 10**6)]),
        # failed tx: underfunded payment rolls back mid-close — the source
        # frame was fee-charged (stored) then mutated in the aborted apply
        T.tx_from_ops(app, a, _seq(app, a) + 1, [T.payment_op(b, 10**15)]),
    ])
    assert codes[:2] == [RC.txSUCCESS, RC.txSUCCESS]
    assert codes[2] == RC.txFAILED
    # and the next close still agrees (post-rollback frame state clean)
    codes = runner.close(lambda app, root: [
        T.tx_from_ops(app, a, _seq(app, a), [T.payment_op(b, 10**6)]),
    ])
    assert codes == [RC.txSUCCESS]


def test_differential_create_then_pay_same_close(runner):
    """An account created by tx1 is the payment destination of tx2 in the
    SAME close: the context must converge on the frame tx1 stored."""
    c = T.get_account("fc-new")
    codes = runner.close(lambda app, root: [
        T.tx_from_ops(app, root, _seq(app, root),
                      [T.create_account_op(c, 10**11)]),
        T.tx_from_ops(app, root, _seq(app, root) + 1,
                      [T.payment_op(c, 10**7)]),
    ])
    assert codes == [RC.txSUCCESS, RC.txSUCCESS]


def test_differential_self_path_payment(runner):
    """destination == source PATH payment (native, empty path) — the op
    holds TWO handles to one account and interleaves credit/store/debit/
    store.  The reference aliases only the signing handle: the fresh
    destination snapshot's credit is overwritten by the stale source
    handle's debit.  The identity map must reproduce that exactly (it
    serves ONLY signing loads), not 'fix' it — a node that kept the
    credit would fork from the network."""
    a = T.get_account("fc-selfpp")
    runner.close(lambda app, root: [
        T.tx_from_ops(app, root, _seq(app, root),
                      [T.create_account_op(a, 10**11)]),
    ])
    codes = runner.close(lambda app, root: [
        T.tx_from_ops(app, a, _seq(app, a), [
            T.op(
                X.OperationType.PATH_PAYMENT,
                X.PathPaymentOp(
                    sendAsset=X.Asset.native(),
                    sendMax=10**7,
                    destination=a.get_public_key(),
                    destAsset=X.Asset.native(),
                    destAmount=10**7,
                    path=[],
                ),
            ),
        ]),
    ])
    assert codes == [RC.txSUCCESS]


def test_differential_signers_merge_inflation(runner):
    a, b = T.get_account("fc-sig"), T.get_account("fc-victim")
    s1 = T.get_account("fc-signer")
    runner.close(lambda app, root: [
        T.tx_from_ops(app, root, _seq(app, root), [
            T.create_account_op(a, 10**12), T.create_account_op(b, 10**11),
        ]),
    ])
    codes = runner.close(lambda app, root: [
        T.tx_from_ops(app, a, _seq(app, a), [
            T.set_options_op(signer=X.Signer(s1.get_public_key(), 1)),
        ]),
        # merge DELETES b mid-close: the identity map must evict, not
        # resurrect, the deleted account
        T.tx_from_ops(app, b, _seq(app, b), [T.merge_op(a)]),
    ])
    assert codes == [RC.txSUCCESS, RC.txSUCCESS]
    codes = runner.close(lambda app, root: [
        T.tx_from_ops(app, a, _seq(app, a), [
            T.set_options_op(inflation_dest=a.get_public_key()),
        ]),
        T.tx_from_ops(app, root, _seq(app, root), [T.inflation_op()]),
    ])
    assert codes[0] == RC.txSUCCESS


def test_differential_offer_crossing(runner):
    """Order-book crossing in one close: account balances mutate through
    shared frames while offers ride the normal (context-less) path."""
    a, b = T.get_account("fc-sell"), T.get_account("fc-buy")
    runner.close(lambda app, root: [
        T.tx_from_ops(app, root, _seq(app, root), [
            T.create_account_op(a, 10**12), T.create_account_op(b, 10**12),
        ]),
    ])

    def mk_usd(app):
        return X.Asset.alphanum4(b"USD", T.root_key_for(app).get_public_key())

    runner.close(lambda app, root: [
        T.tx_from_ops(app, a, _seq(app, a),
                      [T.change_trust_op(mk_usd(app), 10**12)]),
        T.tx_from_ops(app, b, _seq(app, b),
                      [T.change_trust_op(mk_usd(app), 10**12)]),
    ])
    runner.close(lambda app, root: [
        T.tx_from_ops(app, root, _seq(app, root), [
            T.payment_op(b, 10**10, asset=mk_usd(app)),
        ]),
    ])
    codes = runner.close(lambda app, root: [
        T.tx_from_ops(app, a, _seq(app, a), [
            T.manage_offer_op(X.Asset.native(), mk_usd(app), 10**8,
                              X.Price(2, 1)),
        ]),
        T.tx_from_ops(app, b, _seq(app, b), [
            T.manage_offer_op(mk_usd(app), X.Asset.native(), 10**8,
                              X.Price(1, 2)),
        ]),
    ])
    assert codes == [RC.txSUCCESS, RC.txSUCCESS]


class TestContextMechanics:
    def _ctx(self):
        from stellar_tpu.ledger.framecontext import FrameContext

        return FrameContext()

    def test_identity_and_rollback_eviction(self):
        ctx = self._ctx()
        ctx.activate()

        class F:
            _ctx = None

        f = F()
        ctx.adopt(b"k1", f)
        assert ctx.lend(b"k1", mutable=True) is f
        # inside a savepoint: lent frames evict on rollback
        ctx.push_mark()
        assert ctx.lend(b"k1", mutable=True) is f
        g = F()
        ctx.adopt(b"k2", g)
        ctx.rollback_mark()
        assert ctx.lend(b"k1", mutable=True) is None, "lent frame evicted"
        assert ctx.lend(b"k2", mutable=True) is None, "adopted frame evicted"
        assert f._ctx is None and g._ctx is None
        ctx.deactivate()

    def test_release_keeps_outer_scope_accountable(self):
        ctx = self._ctx()
        ctx.activate()

        class F:
            _ctx = None

        ctx.push_mark()   # outer savepoint
        ctx.push_mark()   # inner savepoint
        f = F()
        ctx.adopt(b"k", f)
        ctx.release_mark()   # inner commits into outer scope
        ctx.rollback_mark()  # outer rolls back: inner's frame must evict
        assert ctx.lend(b"k", mutable=True) is None
        ctx.deactivate()

    def test_close_hands_out_one_frame_per_account(self, clock):
        """End-to-end: during a close, fee charging and apply observe the
        same frame object (identity, not just equal state)."""
        from stellar_tpu.ledger.accountframe import AccountFrame

        cfg = T.get_test_config(76)
        app = Application(clock, cfg, new_db=True)
        try:
            root = T.root_key_for(app)
            a = T.get_account("fc-ident")
            lm = app.ledger_manager
            T.close_ledger_on(
                app, lm.last_closed.header.scpValue.closeTime + 5,
                [T.tx_from_ops(app, root, _seq(app, root),
                               [T.create_account_op(a, 10**10)])],
            )
            seen = []
            orig = AccountFrame.load_account.__func__

            def spy(cls, account_id, db, readonly=False, signing=False):
                f = orig(cls, account_id, db, readonly, signing)
                ctx = getattr(db, "_frame_context", None)
                # only in-close SIGNING loads count (the map serves the
                # tx-source plane; tx building loads seqnums too)
                if f is not None and ctx is not None and ctx.active \
                        and signing and not readonly \
                        and account_id == a.get_public_key():
                    seen.append(f)
                return f

            AccountFrame.load_account = classmethod(spy)
            try:
                T.close_ledger_on(
                    app, lm.last_closed.header.scpValue.closeTime + 5,
                    [T.tx_from_ops(app, a, _seq(app, a),
                                   [T.payment_op(root, 10**6)])],
                )
            finally:
                AccountFrame.load_account = classmethod(orig)
            assert len(seen) >= 2, "fee + apply must both load the source"
            assert all(f is seen[0] for f in seen), (
                "close must hand out ONE frame per account"
            )
            ctx = app.database._frame_context
            assert ctx.hits > 0 and not ctx.active
        finally:
            app.database.close()

    def test_readonly_shell_refuses_store(self, clock):
        """A readonly load that hits the identity map gets a live-state
        shell whose stores refuse — the validation plane cannot poison
        the close's working frame or the entry cache."""
        from stellar_tpu.ledger.accountframe import AccountFrame
        from stellar_tpu.ledger.delta import LedgerDelta
        from stellar_tpu.ledger.framecontext import frame_context_of

        cfg = T.get_test_config(77)
        app = Application(clock, cfg, new_db=True)
        try:
            root = T.root_key_for(app)
            db = app.database
            lm = app.ledger_manager
            ctx = frame_context_of(db)
            ctx.activate()
            try:
                pk = root.get_public_key()
                f = AccountFrame.load_account(pk, db, signing=True)  # adopted
                ro = AccountFrame.load_account(
                    pk, db, readonly=True, signing=True
                )
                assert ro is not f and ro.entry is f.entry  # live shell
                delta = LedgerDelta(lm.current.header, db)
                with pytest.raises(RuntimeError, match="read-only"):
                    ro.store_change(delta, db)
            finally:
                ctx.deactivate()
        finally:
            app.database.close()

    def test_savepoint_rollback_evicts_sealed_frames(self, clock):
        """A frame SEALED inside an aborted savepoint scope must be
        evicted from the identity map (its sealed snapshot belongs to the
        rolled-back store), and the next load must observe the pre-scope
        state from the rolled-back cache/SQL planes."""
        from stellar_tpu.ledger.accountframe import AccountFrame
        from stellar_tpu.ledger.delta import LedgerDelta
        from stellar_tpu.ledger.entryframe import key_bytes
        from stellar_tpu.ledger.framecontext import frame_context_of

        cfg = T.get_test_config(79)
        app = Application(clock, cfg, new_db=True)
        try:
            root = T.root_key_for(app)
            db = app.database
            lm = app.ledger_manager
            ctx = frame_context_of(db)
            ctx.activate()
            try:
                pk = root.get_public_key()
                f = AccountFrame.load_account(pk, db, signing=True)
                kb = key_bytes(f.get_key())
                before = f.get_balance()
                delta = LedgerDelta(lm.current.header, db)

                class Boom(Exception):
                    pass

                # the per-tx savepoint must be NESTED inside the close's
                # outer BEGIN (the real apply shape) — only nested scopes
                # push frame-context marks; the outermost BEGIN predates
                # the context activation and unwinds via deactivate
                with db.transaction():
                    with pytest.raises(Boom):
                        with db.transaction():
                            f.mut().balance -= 1000
                            f.store_change(delta, db)
                            assert f._sealed, "store must seal"
                            raise Boom
                    delta.rollback()  # what the aborted tx apply does
                    assert ctx.lend(kb, mutable=True) is None, (
                        "sealed frame must evict with its savepoint"
                    )
                    g = AccountFrame.load_account(pk, db, signing=True)
                    assert g is not f
                    assert g.get_balance() == before, (
                        "post-rollback load must observe pre-scope state"
                    )
            finally:
                ctx.deactivate()
        finally:
            app.database.close()

    def test_stale_context_frame_refuses_store(self, clock):
        """A frame retained past its close cannot write into a later
        ledger (the store_* refusal machinery extended to context-owned
        frames)."""
        from stellar_tpu.ledger.accountframe import AccountFrame
        from stellar_tpu.ledger.delta import LedgerDelta
        from stellar_tpu.ledger.framecontext import frame_context_of

        cfg = T.get_test_config(78)
        app = Application(clock, cfg, new_db=True)
        try:
            root = T.root_key_for(app)
            db = app.database
            lm = app.ledger_manager
            ctx = frame_context_of(db)
            ctx.activate()
            f = AccountFrame.load_account(
                root.get_public_key(), db, signing=True
            )
            ctx.deactivate()  # the close is over
            delta = LedgerDelta(lm.current.header, db)
            with pytest.raises(RuntimeError, match="stale close-scoped"):
                f.store_change(delta, db)
        finally:
            app.database.close()


def _delta_entries(delta):
    """{key_bytes: shared snapshot} over the delta's created+modified
    entries (iter_changed yields (LedgerKey, LedgerEntry, created))."""
    from stellar_tpu.ledger.entryframe import key_bytes

    return {key_bytes(k): e for k, e, _created in delta.iter_changed()}


class TestSealOnStoreCoW:
    """The seal contract (EntryFrame._record / touch): after a store the
    frame's entry IS the one snapshot shared with the delta, the entry
    cache, and the store buffer — no code path may mutate that object.
    Every hostile mutation below must transparently copy-on-write (the
    shared snapshot's bytes stay fixed) or be a provable no-op."""

    def _app(self, clock, instance, cow=True):
        cfg = T.get_test_config(instance)
        cfg.COW_ENTRY_SNAPSHOTS = cow
        return Application(clock, cfg, new_db=True)

    def _stored_root(self, app):
        """(frame, kb, delta): the root account freshly stored (sealed)."""
        from stellar_tpu.ledger.accountframe import AccountFrame
        from stellar_tpu.ledger.delta import LedgerDelta
        from stellar_tpu.ledger.entryframe import key_bytes

        root = T.root_key_for(app)
        db = app.database
        f = AccountFrame.load_account(root.get_public_key(), db)
        delta = LedgerDelta(app.ledger_manager.current.header, db)
        f.store_change(delta, db)
        return f, key_bytes(f.get_key()), delta

    def test_store_seals_and_shares_one_snapshot(self, clock):
        from stellar_tpu.ledger.entryframe import cow_stats

        app = self._app(clock, 86)
        try:
            s0 = cow_stats()
            f, kb, delta = self._stored_root(app)
            assert f._sealed
            assert cow_stats()["seals"] == s0["seals"] + 1
            snap = f.entry
            # ONE object on all three planes
            hit, peeked = f.cache_of(app.database).peek(kb)
            assert hit and peeked is snap
            assert _delta_entries(delta)[kb] is snap
        finally:
            app.database.close()

    @pytest.mark.parametrize("mutate", [
        lambda f: f.mut().balance,
        lambda f: f.add_balance(-1000),
        lambda f: f.set_balance(777),
        lambda f: f.set_seq_num(99),
        lambda f: setattr(f, "last_modified", f.last_modified + 1),
    ], ids=["mut", "add_balance", "set_balance", "set_seq_num",
            "last_modified"])
    def test_hostile_mutation_copies_never_reaches_snapshot(
        self, clock, mutate
    ):
        """Mutating a sealed frame without reload must CoW: the frame gets
        a private copy and the shared snapshot's bytes never move."""
        from stellar_tpu.ledger.entryframe import cow_stats

        app = self._app(clock, 86)
        try:
            f, kb, _delta = self._stored_root(app)
            snap = f.entry
            snap_bytes = snap.to_xdr()
            u0 = cow_stats()["unseals"]
            mutate(f)
            assert f.entry is not snap, "mutation must un-seal via a copy"
            assert not f._sealed
            assert f.account is f.entry.data.value, "typed alias rebound"
            assert snap.to_xdr() == snap_bytes, (
                "the shared snapshot was mutated in place!"
            )
            assert cow_stats()["unseals"] == u0 + 1
            # the cache still serves the (consistent) old snapshot until
            # the next store publishes the new state
            hit, peeked = f.cache_of(app.database).peek(kb)
            assert hit and peeked is snap
        finally:
            app.database.close()

    def test_restore_without_mutation_is_copy_free(self, clock):
        """Re-storing an unmutated sealed frame in the same ledger must
        re-share the same object: the lastModified stamp is a no-op, so
        no CoW copy is paid (the bench shape's fee-charge store)."""
        from stellar_tpu.ledger.entryframe import cow_stats

        app = self._app(clock, 86)
        try:
            f, kb, delta = self._stored_root(app)
            snap = f.entry
            u0 = cow_stats()["unseals"]
            f.store_change(delta, app.database)
            assert f.entry is snap, "same-seq re-store must not copy"
            assert f._sealed
            assert cow_stats()["unseals"] == u0
            hit, peeked = f.cache_of(app.database).peek(kb)
            assert hit and peeked is snap
        finally:
            app.database.close()

    def test_mutate_then_restore_publishes_new_snapshot(self, clock):
        """CoW copy -> mutate -> store: the cache/delta flip to the new
        object and the old snapshot still holds the pre-mutation state
        (peek consistency across a seal)."""
        app = self._app(clock, 86)
        try:
            f, kb, delta = self._stored_root(app)
            old_snap = f.entry
            old_balance = f.get_balance()
            f.mut().balance = old_balance - 5000
            f.store_change(delta, app.database)
            assert f._sealed and f.entry is not old_snap
            hit, peeked = f.cache_of(app.database).peek(kb)
            assert hit and peeked is f.entry
            assert _delta_entries(delta)[kb] is f.entry
            assert old_snap.data.value.balance == old_balance
        finally:
            app.database.close()

    def test_trustline_seal_contract(self, clock):
        """The non-account frame classes ride the same base-class seal:
        TrustFrame mutators (add_balance, set_authorized, mut) must CoW."""
        import stellar_tpu.xdr as X
        from stellar_tpu.ledger.delta import LedgerDelta
        from stellar_tpu.ledger.entryframe import key_bytes
        from stellar_tpu.ledger.trustframe import TrustFrame

        app = self._app(clock, 86)
        try:
            db = app.database
            root_pk = T.root_key_for(app).get_public_key()
            issuer = T.get_account("cow-issuer").get_public_key()
            tf = TrustFrame.make(root_pk, X.Asset.alphanum4(b"USD", issuer))
            tf.mut().limit = 10**12
            tf.set_authorized(True)  # fresh line: flags=0 refuses credits
            delta = LedgerDelta(app.ledger_manager.current.header, db)
            tf.store_add(delta, db)
            assert tf._sealed
            snap = tf.entry
            snap_bytes = snap.to_xdr()
            assert tf.add_balance(10**6)
            assert tf.entry is not snap and not tf._sealed
            assert tf.trust_line is tf.entry.data.value
            assert snap.to_xdr() == snap_bytes
            hit, peeked = tf.cache_of(db).peek(key_bytes(tf.get_key()))
            assert hit and peeked is snap
            tf.store_change(delta, db)
            assert tf._sealed
            tf.set_authorized(True)
            assert not tf._sealed, "set_authorized must CoW too"
        finally:
            app.database.close()

    def test_context_lend_unseals_mutable_only(self, clock):
        """FrameContext.lend: a mutable hand-out of a sealed frame pays
        the CoW copy; a readonly hand-out keeps sharing the sealed entry
        (and the memoized shell is rebuilt after an un-seal)."""
        from stellar_tpu.ledger.accountframe import AccountFrame
        from stellar_tpu.ledger.delta import LedgerDelta
        from stellar_tpu.ledger.framecontext import frame_context_of

        app = self._app(clock, 86)
        try:
            db = app.database
            pk = T.root_key_for(app).get_public_key()
            ctx = frame_context_of(db)
            ctx.activate()
            try:
                f = AccountFrame.load_account(pk, db, signing=True)
                delta = LedgerDelta(app.ledger_manager.current.header, db)
                f.store_change(delta, db)
                assert f._sealed
                sealed_entry = f.entry
                ro = AccountFrame.load_account(
                    pk, db, readonly=True, signing=True
                )
                assert ro.entry is sealed_entry, (
                    "readonly shell shares the sealed snapshot (no copy)"
                )
                assert f._sealed, "readonly lend must not un-seal"
                g = AccountFrame.load_account(pk, db, signing=True)
                assert g is f and not f._sealed
                assert f.entry is not sealed_entry, "mutable lend CoWs"
                ro2 = AccountFrame.load_account(
                    pk, db, readonly=True, signing=True
                )
                assert ro2.entry is f.entry, (
                    "shell rebuilt over the live entry after the un-seal"
                )
            finally:
                ctx.deactivate()
        finally:
            app.database.close()

    def test_cow_off_restores_eager_copies(self, clock):
        """COW_ENTRY_SNAPSHOTS=False: stores never seal and the cache
        line is an independent deep copy of the frame's entry."""
        from stellar_tpu.ledger.entryframe import cow_stats

        app = self._app(clock, 87, cow=False)
        try:
            s0 = cow_stats()["seals"]
            f, kb, _delta = self._stored_root(app)
            assert not f._sealed
            assert cow_stats()["seals"] == s0
            hit, peeked = f.cache_of(app.database).peek(kb)
            assert hit and peeked is not f.entry
            assert peeked.to_xdr() == f.entry.to_xdr()
        finally:
            app.database.close()
