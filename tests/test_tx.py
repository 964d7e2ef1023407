"""Transaction suite (reference style: src/transactions/*Tests.cpp against a
standalone app with in-memory sqlite, SURVEY.md §4 layer 3)."""

import pytest

import stellar_tpu.xdr as X
from stellar_tpu.crypto import SecretKey
from stellar_tpu.main.application import Application
from stellar_tpu.tx import testutils as T
from stellar_tpu.util import VIRTUAL_TIME, VirtualClock

RC = X.TransactionResultCode


@pytest.fixture
def clock():
    c = VirtualClock(VIRTUAL_TIME)
    yield c
    c.shutdown()


@pytest.fixture
def app(clock, request):
    # indirect-parameterizable over the SIGNATURE_BACKEND knob: most tests
    # run cpu-only; the node-level batch-verify tests run both backends
    # (the tpu backend's XLA kernel runs on the CPU mesh in tests)
    backend = getattr(request, "param", "cpu")
    cfg = T.get_test_config(backend=backend)
    if backend == "tpu":
        cfg.TPU_CPU_CUTOVER = 0  # small test batches must hit the device path
    a = Application(clock, cfg, new_db=True)
    yield a
    a.database.close()


both_backends = pytest.mark.parametrize(
    "app", ["cpu", "tpu"], indirect=True
)


@pytest.fixture
def root(app):
    return T.root_key_for(app)


def root_seq(app, root):
    from stellar_tpu.ledger.accountframe import AccountFrame

    return AccountFrame.load_account(root.get_public_key(), app.database).get_seq_num()


def fund(app, root, dest, amount=None):
    amount = amount or 10_000 * 10**7
    tx = T.tx_from_ops(app, root, root_seq(app, root) + 1,
                       [T.create_account_op(dest, amount)])
    T.apply_tx(app, tx, expect_code=RC.txSUCCESS)
    return dest


class TestGenesis:
    def test_master_account_created(self, app, root):
        from stellar_tpu.ledger.accountframe import AccountFrame

        master = AccountFrame.load_account(root.get_public_key(), app.database)
        assert master is not None
        assert master.get_balance() == 10**18
        assert app.ledger_manager.last_closed.header.ledgerSeq == 1
        assert app.ledger_manager.current.header.ledgerSeq == 2


class TestCreateAccount:
    def test_create_and_balance(self, app, root):
        """PaymentTests.cpp:110-113 ("Create account" / "Success")."""
        dest = T.get_account(1)
        fund(app, root, dest, 5000 * 10**7)
        from stellar_tpu.ledger.accountframe import AccountFrame

        acc = AccountFrame.load_account(dest.get_public_key(), app.database)
        assert acc.get_balance() == 5000 * 10**7
        # starting seq = ledgerSeq << 32
        assert acc.get_seq_num() == app.ledger_manager.current.header.ledgerSeq << 32

    def test_create_below_reserve_fails(self, app, root):
        """PaymentTests.cpp:126-133 ("Amount too small to create account")."""
        dest = T.get_account(1)
        tx = T.tx_from_ops(
            app, root, root_seq(app, root) + 1, [T.create_account_op(dest, 1)]
        )
        T.apply_tx(app, tx, expect_code=RC.txFAILED)
        assert (
            T.inner_op_code(tx)
            == X.CreateAccountResultCode.CREATE_ACCOUNT_LOW_RESERVE
        )

    def test_create_duplicate_fails(self, app, root):
        """PaymentTests.cpp:114-120 ("Account already exists")."""
        dest = T.get_account(1)
        fund(app, root, dest)
        tx = T.tx_from_ops(
            app, root, root_seq(app, root) + 1,
            [T.create_account_op(dest, 10**10)],
        )
        T.apply_tx(app, tx, expect_code=RC.txFAILED)
        assert (
            T.inner_op_code(tx)
            == X.CreateAccountResultCode.CREATE_ACCOUNT_ALREADY_EXIST
        )

    def test_create_underfunded_source_fails(self, app, root):
        """PaymentTests.cpp:121-125 ("Not enough funds (source)") — a thin
        source cannot fund a creation larger than its balance."""
        from stellar_tpu.ledger.accountframe import AccountFrame

        thin = fund(app, root, T.get_account(2), amount=60 * 10**7)
        dest = T.get_account(3)
        seq = AccountFrame.load_account(
            thin.get_public_key(), app.database
        ).get_seq_num()
        tx = T.tx_from_ops(
            app, thin, seq + 1, [T.create_account_op(dest, 10**12)]
        )
        T.apply_tx(app, tx, expect_code=RC.txFAILED)
        assert (
            T.inner_op_code(tx)
            == X.CreateAccountResultCode.CREATE_ACCOUNT_UNDERFUNDED
        )
        assert AccountFrame.load_account(dest.get_public_key(), app.database) is None


class TestPayment:
    def test_native_payment(self, app, root):
        """PaymentTests.cpp:134-148 ("send XLM to an existing account")."""
        a = fund(app, root, T.get_account(1))
        b = fund(app, root, T.get_account(2))
        tx = T.tx_from_ops(app, a, (2 << 32) + 1, [T.payment_op(b, 10**7)])
        T.apply_tx(app, tx, expect_code=RC.txSUCCESS)
        from stellar_tpu.ledger.accountframe import AccountFrame

        bacc = AccountFrame.load_account(b.get_public_key(), app.database)
        assert bacc.get_balance() == 10_000 * 10**7 + 10**7

    def test_payment_underfunded(self, app, root):
        a = fund(app, root, T.get_account(1), 300 * 10**7)
        b = fund(app, root, T.get_account(2))
        tx = T.tx_from_ops(app, a, (2 << 32) + 1, [T.payment_op(b, 10**12)])
        T.apply_tx(app, tx, expect_code=RC.txFAILED)
        assert T.inner_op_code(tx) == X.PaymentResultCode.PAYMENT_UNDERFUNDED

    def test_payment_to_missing_account(self, app, root):
        """PaymentTests.cpp:159-166 ("send XLM to a new account (no destination)")."""
        a = fund(app, root, T.get_account(1))
        ghost = T.get_account(99)
        tx = T.tx_from_ops(app, a, (2 << 32) + 1, [T.payment_op(ghost, 10**7)])
        T.apply_tx(app, tx, expect_code=RC.txFAILED)
        assert T.inner_op_code(tx) == X.PaymentResultCode.PAYMENT_NO_DESTINATION

    def test_bad_signature_rejected(self, app, root):
        a = fund(app, root, T.get_account(1))
        b = fund(app, root, T.get_account(2))
        evil = T.get_account(666)
        tx_xdr = X.Transaction(
            sourceAccount=a.get_public_key(),
            fee=100,
            seqNum=(2 << 32) + 1,
            memo=X.Memo.none(),
            operations=[T.payment_op(b, 10**7)],
        )
        from stellar_tpu.tx.frame import TransactionFrame

        frame = TransactionFrame(app.network_id, X.TransactionEnvelope(tx_xdr, []))
        frame.add_signature(evil)  # signed by the wrong key
        assert not frame.check_valid(app, 0)
        assert frame.get_result_code() == RC.txBAD_AUTH

    def test_sequence_gap_rejected(self, app, root):
        a = fund(app, root, T.get_account(1))
        b = fund(app, root, T.get_account(2))
        tx = T.tx_from_ops(app, a, (2 << 32) + 7, [T.payment_op(b, 10**7)])
        assert not tx.check_valid(app, 0)
        assert tx.get_result_code() == RC.txBAD_SEQ

    def test_fee_charged_even_on_failure(self, app, root):
        a = fund(app, root, T.get_account(1), 500 * 10**7)
        b = fund(app, root, T.get_account(2))
        from stellar_tpu.ledger.accountframe import AccountFrame

        before = AccountFrame.load_account(a.get_public_key(), app.database).get_balance()
        tx = T.tx_from_ops(app, a, (2 << 32) + 1, [T.payment_op(b, 10**13)])
        T.apply_tx(app, tx, expect_code=RC.txFAILED)
        AccountFrame.cache_of(app.database).clear()
        after = AccountFrame.load_account(a.get_public_key(), app.database).get_balance()
        assert after == before - 100  # fee gone, payment rolled back


class TestMultisig:
    def test_add_signer_and_threshold(self, app, root):
        a = fund(app, root, T.get_account(1))
        s1 = T.get_account(11)
        # add signer weight 1, raise med threshold to 2 => payments need both
        tx = T.tx_from_ops(
            app, a, (2 << 32) + 1,
            [T.set_options_op(med=2, high=2,
                              signer=X.Signer(s1.get_public_key(), 1))],
        )
        T.apply_tx(app, tx, expect_code=RC.txSUCCESS)
        b = fund(app, root, T.get_account(2))
        # master alone (weight 1) insufficient for medium=2
        tx = T.tx_from_ops(app, a, (2 << 32) + 2, [T.payment_op(b, 10**7)])
        assert not tx.check_valid(app, 0)
        assert tx.result.result.value[0].type == X.OperationResultCode.opBAD_AUTH
        # master + signer => passes
        tx = T.tx_from_ops(app, a, (2 << 32) + 2, [T.payment_op(b, 10**7)])
        tx.add_signature(s1)
        assert tx.check_valid(app, 0)

    def test_extra_signature_rejected(self, app, root):
        a = fund(app, root, T.get_account(1))
        b = fund(app, root, T.get_account(2))
        stranger = T.get_account(12)
        tx = T.tx_from_ops(app, a, (2 << 32) + 1, [T.payment_op(b, 10**7)])
        tx.add_signature(stranger)  # unused signature
        assert not tx.check_valid(app, 0)
        assert tx.get_result_code() == RC.txBAD_AUTH_EXTRA


class TestTrustAndCredit:
    def test_trust_and_credit_payment(self, app, root):
        """PaymentTests.cpp:236-267 ("with trust" / "positive")."""
        issuer = fund(app, root, T.get_account(1))
        holder = fund(app, root, T.get_account(2))
        usd = X.Asset.alphanum4(b"USD", issuer.get_public_key())
        T.apply_tx(
            app,
            T.tx_from_ops(app, holder, (2 << 32) + 1,
                          [T.change_trust_op(usd, 10**10)]),
            expect_code=RC.txSUCCESS,
        )
        T.apply_tx(
            app,
            T.tx_from_ops(app, issuer, (2 << 32) + 1,
                          [T.payment_op(holder, 500, usd)]),
            expect_code=RC.txSUCCESS,
        )
        from stellar_tpu.ledger.trustframe import TrustFrame

        line = TrustFrame.load_trust_line(holder.get_public_key(), usd, app.database)
        assert line.get_balance() == 500

    def test_payment_without_trust_fails(self, app, root):
        """PaymentTests.cpp:223-235 ("credit sent to new account" /
        "credit payment with no trust")."""
        issuer = fund(app, root, T.get_account(1))
        holder = fund(app, root, T.get_account(2))
        usd = X.Asset.alphanum4(b"USD", issuer.get_public_key())
        tx = T.tx_from_ops(
            app, issuer, (2 << 32) + 1, [T.payment_op(holder, 500, usd)]
        )
        T.apply_tx(app, tx, expect_code=RC.txFAILED)
        assert T.inner_op_code(tx) == X.PaymentResultCode.PAYMENT_NO_TRUST

    def test_auth_required_flow(self, app, root):
        issuer = fund(app, root, T.get_account(1))
        holder = fund(app, root, T.get_account(2))
        # issuer requires auth
        T.apply_tx(
            app,
            T.tx_from_ops(app, issuer, (2 << 32) + 1,
                          [T.set_options_op(set_flags=0x1)]),
            expect_code=RC.txSUCCESS,
        )
        usd = X.Asset.alphanum4(b"USD", issuer.get_public_key())
        T.apply_tx(
            app,
            T.tx_from_ops(app, holder, (2 << 32) + 1,
                          [T.change_trust_op(usd, 10**10)]),
            expect_code=RC.txSUCCESS,
        )
        # unauthorized: payment fails
        tx = T.tx_from_ops(
            app, issuer, (2 << 32) + 2, [T.payment_op(holder, 5, usd)]
        )
        T.apply_tx(app, tx, expect_code=RC.txFAILED)
        assert T.inner_op_code(tx) == X.PaymentResultCode.PAYMENT_NOT_AUTHORIZED
        # authorize, then it works
        T.apply_tx(
            app,
            T.tx_from_ops(app, issuer, (2 << 32) + 3,
                          [T.allow_trust_op(holder, b"USD", True)]),
            expect_code=RC.txSUCCESS,
        )
        T.apply_tx(
            app,
            T.tx_from_ops(app, issuer, (2 << 32) + 4,
                          [T.payment_op(holder, 5, usd)]),
            expect_code=RC.txSUCCESS,
        )


class TestOffersAndPathPayment:
    def _setup_market(self, app, root):
        issuer = fund(app, root, T.get_account(1))
        seller = fund(app, root, T.get_account(2))
        buyer = fund(app, root, T.get_account(3))
        usd = X.Asset.alphanum4(b"USD", issuer.get_public_key())
        for who in (seller, buyer):
            T.apply_tx(
                app,
                T.tx_from_ops(app, who, (2 << 32) + 1,
                              [T.change_trust_op(usd, 10**12)]),
                expect_code=RC.txSUCCESS,
            )
        T.apply_tx(
            app,
            T.tx_from_ops(app, issuer, (2 << 32) + 1,
                          [T.payment_op(seller, 10**6, usd)]),
            expect_code=RC.txSUCCESS,
        )
        return issuer, seller, buyer, usd

    def test_manage_offer_created(self, app, root):
        issuer, seller, buyer, usd = self._setup_market(app, root)
        # seller sells USD for XLM at 2 XLM/USD
        tx = T.tx_from_ops(
            app, seller, (2 << 32) + 2,
            [T.manage_offer_op(usd, X.Asset.native(), 1000, X.Price(2, 1))],
        )
        T.apply_tx(app, tx, expect_code=RC.txSUCCESS)
        res = T.op_result_of(tx).value.value
        assert res.type == X.ManageOfferResultCode.MANAGE_OFFER_SUCCESS
        assert res.value.offer.type == X.ManageOfferEffect.MANAGE_OFFER_CREATED

    def test_offer_crossing(self, app, root):
        issuer, seller, buyer, usd = self._setup_market(app, root)
        T.apply_tx(
            app,
            T.tx_from_ops(
                app, seller, (2 << 32) + 2,
                [T.manage_offer_op(usd, X.Asset.native(), 1000, X.Price(2, 1))],
            ),
            expect_code=RC.txSUCCESS,
        )
        # buyer sells XLM for USD at matching price -> crosses
        tx = T.tx_from_ops(
            app, buyer, (2 << 32) + 2,
            [T.manage_offer_op(X.Asset.native(), usd, 2000, X.Price(1, 2))],
        )
        T.apply_tx(app, tx, expect_code=RC.txSUCCESS)
        res = T.op_result_of(tx).value.value
        assert res.value.offersClaimed, "expected the resting offer to be taken"
        from stellar_tpu.ledger.trustframe import TrustFrame

        line = TrustFrame.load_trust_line(buyer.get_public_key(), usd, app.database)
        assert line.get_balance() == 1000

    def test_path_payment_through_book(self, app, root):
        issuer, seller, buyer, usd = self._setup_market(app, root)
        T.apply_tx(
            app,
            T.tx_from_ops(
                app, seller, (2 << 32) + 2,
                [T.manage_offer_op(usd, X.Asset.native(), 1000, X.Price(2, 1))],
            ),
            expect_code=RC.txSUCCESS,
        )
        # buyer pays holder 100 USD, sourced from native through the book
        holder = fund(app, root, T.get_account(4))
        T.apply_tx(
            app,
            T.tx_from_ops(app, holder, (2 << 32) + 1,
                          [T.change_trust_op(usd, 10**12)]),
            expect_code=RC.txSUCCESS,
        )
        tx = T.tx_from_ops(
            app, buyer, (2 << 32) + 2,
            [T.path_payment_op(holder, X.Asset.native(), 10**6, usd, 100)],
        )
        T.apply_tx(app, tx, expect_code=RC.txSUCCESS)
        from stellar_tpu.ledger.trustframe import TrustFrame

        line = TrustFrame.load_trust_line(holder.get_public_key(), usd, app.database)
        assert line.get_balance() == 100


class TestMerge:
    def test_merge_moves_balance(self, app, root):
        """MergeTests.cpp:119-126 ("success - basic")."""
        a = fund(app, root, T.get_account(1), 1000 * 10**7)
        b = fund(app, root, T.get_account(2))
        from stellar_tpu.ledger.accountframe import AccountFrame

        a_bal = AccountFrame.load_account(a.get_public_key(), app.database).get_balance()
        tx = T.tx_from_ops(app, a, (2 << 32) + 1, [T.merge_op(b)])
        T.apply_tx(app, tx, expect_code=RC.txSUCCESS)
        assert AccountFrame.load_account(a.get_public_key(), app.database) is None
        AccountFrame.cache_of(app.database).clear()
        b_acc = AccountFrame.load_account(b.get_public_key(), app.database)
        assert b_acc.get_balance() == 10_000 * 10**7 + a_bal - 100  # minus fee

    def test_merge_with_trustline_fails(self, app, root):
        """MergeTests.cpp:85-94 ("With sub entries" / "account has trust line")."""
        issuer = fund(app, root, T.get_account(1))
        a = fund(app, root, T.get_account(2))
        usd = X.Asset.alphanum4(b"USD", issuer.get_public_key())
        T.apply_tx(
            app,
            T.tx_from_ops(app, a, (2 << 32) + 1, [T.change_trust_op(usd, 10**9)]),
            expect_code=RC.txSUCCESS,
        )
        tx = T.tx_from_ops(app, a, (2 << 32) + 2, [T.merge_op(issuer)])
        T.apply_tx(app, tx, expect_code=RC.txFAILED)
        assert (
            T.inner_op_code(tx)
            == X.AccountMergeResultCode.ACCOUNT_MERGE_HAS_SUB_ENTRIES
        )


class TestLedgerClose:
    def test_close_ledger_with_txset(self, app, root):
        from stellar_tpu.herder.ledgerclose import LedgerCloseData
        from stellar_tpu.herder.txset import TxSetFrame

        a = T.get_account(1)
        lm = app.ledger_manager
        tx = T.tx_from_ops(
            app, root, root_seq(app, root) + 1,
            [T.create_account_op(a, 10**10)],
        )
        txset = TxSetFrame(lm.last_closed.hash, [tx])
        assert txset.check_valid(app)
        sv = X.StellarValue(txset.get_contents_hash(), 1, [], 0)
        lm.close_ledger(LedgerCloseData(lm.current.header.ledgerSeq, txset, sv))
        assert lm.last_closed.header.ledgerSeq == 2
        assert lm.last_closed.header.scpValue.closeTime == 1
        from stellar_tpu.ledger.accountframe import AccountFrame

        assert AccountFrame.load_account(a.get_public_key(), app.database) is not None
        # header chain stored
        from stellar_tpu.ledger.headerframe import LedgerHeaderFrame

        h2 = LedgerHeaderFrame.load_by_sequence(app.database, 2)
        assert h2.header.previousLedgerHash is not None
        h1 = LedgerHeaderFrame.load_by_sequence(app.database, 1)
        assert h2.header.previousLedgerHash == h1.get_hash()

    def test_close_rejects_wrong_prev_hash(self, app, root):
        from stellar_tpu.herder.ledgerclose import LedgerCloseData
        from stellar_tpu.herder.txset import TxSetFrame

        lm = app.ledger_manager
        txset = TxSetFrame(b"\x00" * 32, [])
        sv = X.StellarValue(txset.get_contents_hash(), 1, [], 0)
        with pytest.raises(RuntimeError):
            lm.close_ledger(LedgerCloseData(2, txset, sv))

    def test_txset_invalid_with_bad_seq(self, app, root):
        from stellar_tpu.herder.txset import TxSetFrame

        a = T.get_account(1)
        lm = app.ledger_manager
        tx = T.tx_from_ops(
            app, root, root_seq(app, root) + 5,  # gap
            [T.create_account_op(a, 10**10)],
        )
        txset = TxSetFrame(lm.last_closed.hash, [tx])
        assert not txset.check_valid(app)


class TestBaselineMeasurementConfigs:
    """The two BASELINE.json measurement configs not covered elsewhere:
    3-of-5 multisig envelopes and a mixed-op TxSet through a real close."""

    @both_backends
    def test_3_of_5_multisig_txset_through_batch_verify(self, app, root):
        a = fund(app, root, T.get_account(1), amount=10**11)
        signers = [T.get_account(20 + i) for i in range(5)]
        # add the five weight-1 signers first, THEN raise the thresholds —
        # ops apply sequentially, so raising med/high in the first op would
        # lock the remaining ops out (opBAD_AUTH)
        ops = [
            T.set_options_op(signer=X.Signer(s.get_public_key(), 1))
            for s in signers
        ] + [T.set_options_op(med=3, high=3)]
        tx = T.tx_from_ops(app, a, (2 << 32) + 1, ops)
        T.apply_tx(app, tx, expect_code=RC.txSUCCESS)
        b = fund(app, root, T.get_account(2))

        from stellar_tpu.herder.txset import TxSetFrame

        lm = app.ledger_manager
        txs = []
        for j in range(6):
            t = T.tx_from_ops(
                app, a, (2 << 32) + 2 + j, [T.payment_op(b, 10**6)]
            )
            t.envelope.signatures = []  # drop the master signature
            for s in signers[j % 3 : j % 3 + 3]:  # 3 distinct signers
                t.add_signature(s)
            txs.append(t)
        # one more with only 2 signers: must be trimmed
        bad = T.tx_from_ops(app, a, (2 << 32) + 8, [T.payment_op(b, 10**6)])
        bad.envelope.signatures = []
        for s in signers[:2]:
            bad.add_signature(s)
        txs.append(bad)
        txset = TxSetFrame(lm.last_closed.hash, txs)
        txset.sort_for_hash()
        trimmed = txset.trim_invalid(app)
        assert trimmed == [bad]
        assert len(txset.transactions) == 6
        assert txset.check_valid(app)

    @both_backends
    def test_mixed_op_txset_closes(self, app, root):
        """PathPayment, ManageOffer, SetOptions, CreateAccount in one set
        (the BASELINE.json mixed-op config), applied via a real close."""
        from stellar_tpu.herder.ledgerclose import LedgerCloseData
        from stellar_tpu.herder.txset import TxSetFrame
        from stellar_tpu.xdr.ledger import StellarValue

        lm = app.ledger_manager
        issuer = fund(app, root, T.get_account(1), amount=10**11)
        trader = fund(app, root, T.get_account(2), amount=10**11)
        usd = X.Asset.alphanum4(b"USD", issuer.get_public_key())
        # prepare: trustline + issued USD
        T.apply_tx(
            app,
            T.tx_from_ops(app, trader, (2 << 32) + 1,
                          [T.change_trust_op(usd, 10**12)]),
            expect_code=RC.txSUCCESS,
        )
        T.apply_tx(
            app,
            T.tx_from_ops(app, issuer, (2 << 32) + 1,
                          [T.payment_op(trader, 10**9, asset=usd)]),
            expect_code=RC.txSUCCESS,
        )
        new_acc = T.get_account(3)
        txs = [
            T.tx_from_ops(app, root, root_seq(app, root) + 1,
                          [T.create_account_op(new_acc, 10**9)]),
            T.tx_from_ops(app, trader, (2 << 32) + 2,
                          [T.manage_offer_op(usd, X.Asset.native(), 10**7,
                                             X.Price(1, 2))]),
            T.tx_from_ops(app, issuer, (2 << 32) + 2,
                          [T.set_options_op(home_domain="example.com")]),
        ]
        txset = TxSetFrame(lm.last_closed.hash, txs)
        txset.sort_for_hash()
        assert txset.check_valid(app)
        sv = StellarValue(
            txset.get_contents_hash(),
            lm.last_closed.header.scpValue.closeTime + 5, [], 0
        )
        seq_before = lm.last_closed.header.ledgerSeq
        lm.close_ledger(LedgerCloseData(lm.current.header.ledgerSeq, txset, sv))
        assert lm.last_closed.header.ledgerSeq == seq_before + 1
        from stellar_tpu.ledger.accountframe import AccountFrame

        assert AccountFrame.load_account(
            new_acc.get_public_key(), app.database
        ).get_balance() == 10**9
        n_offers = app.database.query_one("SELECT COUNT(*) FROM offers")[0]
        assert n_offers == 1


def test_op_shares_tx_signing_account(app, root):
    """An op whose source is the tx source must get the SAME AccountFrame
    object as the parent tx (reference: TransactionFrame::loadAccount reusing
    mSigningAccount, src/transactions/TransactionFrame.cpp)."""
    from stellar_tpu.ledger.accountframe import AccountFrame

    a = SecretKey.pseudo_random_for_testing(900)
    fund(app, root, a)
    seq = AccountFrame.load_account(a.get_public_key(), app.database).get_seq_num()
    tx = T.tx_from_ops(app, a, seq + 1, [T.payment_op(root, 1000)])
    assert tx.load_account(app.database) is not None
    op = tx.operations[0]
    assert op.load_account(app.database)
    assert op.source_account is tx.signing_account


def test_cpu_and_tpu_backends_close_identical_ledgers():
    """End-to-end equivalence: the same txset closed by a cpu-backed and a
    tpu-backed Application must produce bit-identical ledger headers (the
    system-level contract behind the differential kernel suite — the
    backend knob may change WHERE signatures verify, never any state)."""
    from stellar_tpu.herder.txset import TxSetFrame

    hashes = []
    for backend in ("cpu", "tpu"):
        clock = VirtualClock(VIRTUAL_TIME)
        try:
            cfg = T.get_test_config(83, backend=backend)
            cfg.TPU_CPU_CUTOVER = 0
            app = Application(clock, cfg, new_db=True)
            try:
                root = T.root_key_for(app)
                a = fund(app, root, T.get_account(1), amount=10**11)
                b = fund(app, root, T.get_account(2), amount=10**11)
                lm = app.ledger_manager
                txs = [
                    T.tx_from_ops(
                        app, a, (2 << 32) + 1 + j, [T.payment_op(b, 10**6)]
                    )
                    for j in range(5)
                ]
                # one bad-signature tx: must be trimmed identically
                bad = T.tx_from_ops(app, a, (2 << 32) + 9,
                                    [T.payment_op(b, 10**6)])
                bad.envelope.signatures[0].signature = bytes(64)
                txs.append(bad)
                txset = TxSetFrame(lm.last_closed.hash, txs)
                txset.sort_for_hash()
                assert txset.trim_invalid(app) == [bad]
                T.close_ledger_on(
                    app,
                    lm.last_closed.header.scpValue.closeTime + 5,
                    txset.transactions,
                )
                hashes.append(lm.last_closed.hash)
            finally:
                app.database.close()
        finally:
            clock.shutdown()
    assert hashes[0] == hashes[1]


def test_paranoid_mode_audits_every_close(clock):
    """PARANOID_MODE (LedgerDelta.check_against_database, the reference's
    --paranoid ledger audit at LedgerManagerImpl.cpp:705): mixed-op closes
    pass the delta-vs-DB comparison; a row corrupted behind the delta's
    back makes the close raise instead of committing divergent state."""
    cfg = T.get_test_config(84)
    cfg.PARANOID_MODE = True
    app = Application(clock, cfg, new_db=True)
    try:
        root = T.root_key_for(app)
        lm = app.ledger_manager
        a = fund(app, root, T.get_account(1), amount=10**11)
        b = fund(app, root, T.get_account(2), amount=10**11)
        # audited close with a payment + a trustline + an offer, so every
        # entry-type arm of check_against_database runs
        usd = X.Asset.alphanum4(b"USD", a.get_public_key())
        txs = [
            T.tx_from_ops(app, a, (2 << 32) + 1, [T.payment_op(b, 10**6)]),
            T.tx_from_ops(app, b, (2 << 32) + 1,
                          [T.change_trust_op(usd, 10**10)]),
            T.tx_from_ops(app, a, (2 << 32) + 2, [T.manage_offer_op(
                X.Asset.native(), usd, 10**6, X.Price(1, 1))]),
        ]
        seq_before = lm.last_closed.header.ledgerSeq
        T.close_ledger_on(
            app, lm.last_closed.header.scpValue.closeTime + 5, txs
        )
        assert lm.last_closed.header.ledgerSeq == seq_before + 1

        # negative: the audit exists to catch a delta/SQL divergence bug —
        # simulate a "missed SQL write" (the delta and cache record the
        # new entry, the row never lands) and the close must raise instead
        # of committing divergent state.  With ENTRY_WRITE_BUFFER on the
        # per-tx write path is the batched flush (upsert_batch); drop the
        # target's row there.
        from stellar_tpu.ledger.accountframe import AccountFrame

        orig_upsert = AccountFrame.upsert_batch.__func__
        dropped = []
        target = a.get_public_key()  # the payment DEST: its only write

        def flaky_upsert(cls, db, entries, signers_dirty):
            kept = []
            for e, dirty in zip(entries, signers_dirty):
                if e.data.value.accountID == target and not dropped:
                    dropped.append(target)
                    continue  # lose exactly one row from the flush
                kept.append((e, dirty))
            orig_upsert(cls, db, *zip(*kept))

        AccountFrame.upsert_batch = classmethod(flaky_upsert)
        try:
            bad = [T.tx_from_ops(app, b, (2 << 32) + 2,
                                 [T.payment_op(a, 10**6)])]
            with pytest.raises(RuntimeError, match="delta-vs-database"):
                T.close_ledger_on(
                    app, lm.last_closed.header.scpValue.closeTime + 5, bad
                )
        finally:
            AccountFrame.upsert_batch = classmethod(orig_upsert)
        assert dropped, "the fault was never injected"

        # same audit, write-through plane: with the buffer off the per-store
        # _persist is the write path — lose one there instead
        app.config.ENTRY_WRITE_BUFFER = False
        orig_persist = AccountFrame._persist
        dropped2 = []

        def flaky_persist(self, db, insert):
            if self.get_id() == target and not dropped2:
                dropped2.append(self.get_id())
                return
            orig_persist(self, db, insert)

        AccountFrame._persist = flaky_persist
        try:
            bad = [T.tx_from_ops(app, b, (2 << 32) + 2,
                                 [T.payment_op(a, 10**6)])]
            with pytest.raises(RuntimeError, match="delta-vs-database"):
                T.close_ledger_on(
                    app, lm.last_closed.header.scpValue.closeTime + 5, bad
                )
        finally:
            AccountFrame._persist = orig_persist
        assert dropped2, "the write-through fault was never injected"
    finally:
        app.database.close()


def test_wedged_device_dispatch_falls_back_to_host_and_latches():
    """A wedged accelerator dispatch (hung transport) must never stall a
    verify_batch caller — SCP flushes run on the main crank and ledger
    close joins the prewarm.  The backend finishes on host within
    DEVICE_TIMEOUT, then LATCHES onto host so a persistent outage costs
    one bounded stall per RETRY_INTERVAL, not one per batch.

    The latch is scoped PER CALLER CLASS (ISSUE r10): a stall observed by
    the pipelined async prewarm must not silently route the synchronous
    close-path batches onto host — each class probes (and latches) the
    device independently, and flips are metered per class."""
    import threading
    import time as _time

    from stellar_tpu.crypto.sigbackend import (
        CALLER_CLOSE,
        CALLER_PIPELINE,
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
    sk = SecretKey.pseudo_random_for_testing(3)
    msg = b"wedge"
    items = [(sk.public_raw, msg, sk.sign(msg))]
    t0 = _time.perf_counter()
    # a stalled PIPELINE prewarm latches the pipeline class...
    assert be.verify_batch(items, caller=CALLER_PIPELINE) == [True]
    assert 0.2 <= _time.perf_counter() - t0 < 5
    assert WedgedVerifier.calls == 1
    assert be.n_latch_flips == {CALLER_PIPELINE: 1}
    # ...latched: the next pipeline batch goes straight to host
    t0 = _time.perf_counter()
    assert be.verify_batch(items, caller=CALLER_PIPELINE) == [True]
    assert _time.perf_counter() - t0 < 0.1
    assert WedgedVerifier.calls == 1
    assert be.n_wedge_fallback_items == 2
    # ...but the synchronous close-path class still probes the device
    # (and latches ITSELF after its own observed stall)
    assert be.verify_batch(items, caller=CALLER_CLOSE) == [True]
    assert WedgedVerifier.calls == 2
    assert be.n_latch_flips == {CALLER_PIPELINE: 1, CALLER_CLOSE: 1}
    assert be.verify_batch(items, caller=CALLER_CLOSE) == [True]
    assert WedgedVerifier.calls == 2  # close class now latched too
    # after the latch expires the device is probed again (and re-latches)
    be._wedged_until = {}
    assert be.verify_batch(items, caller=CALLER_PIPELINE) == [True]
    assert WedgedVerifier.calls == 3
    assert be.n_latch_flips[CALLER_PIPELINE] == 2


@pytest.mark.parametrize("site", ["cutover", "stall-then-latch", "torsion"])
def test_host_verify_counts_libsodiums_own_seconds(site):
    """stats() ``host_verify``: batches, items and the seconds inside
    libsodium at every site that opens ``sig.host_verify`` — the cutover,
    the device stall and the wedge latch — read inside the span, so the
    spans' durations hold them; ``items`` is ``caller_items``' host column
    summed over callers, and a host torsion batch adds nothing."""
    import threading

    from stellar_tpu.crypto.sigbackend import (
        CALLER_CLOSE,
        CALLER_INGEST,
        CALLER_OVERLAY,
        TpuSigBackend,
    )
    from stellar_tpu.trace import Tracer

    be = TpuSigBackend.__new__(TpuSigBackend)  # skip JAX verifier init
    be._tracer = Tracer(enabled=True)
    be.cpu_cutover = 8 if site == "cutover" else 0
    be.n_cutover_items = 0
    be.n_cutover_torsion = 0
    be.n_wedge_fallback_items = 0
    be._wedged_until = {}
    be.n_latch_flips = {}
    be._wedge_lock = threading.Lock()
    be.DEVICE_TIMEOUT = 0.2

    class WedgedVerifier:
        def cold_buckets(self, n, host_assist=True):
            return 0

        def chunk_count(self, n, host_assist=True):
            return 1

        def verify(self, items):
            threading.Event().wait()  # wedged forever

        verify_torsion = verify

        def stats(self):
            return {}

    be._verifier = WedgedVerifier()
    sks = [SecretKey.pseudo_random_for_testing(40 + i) for i in range(5)]
    items = [(sk.public_raw, b"hv-%d" % i, sk.sign(b"hv-%d" % i)) for i, sk in enumerate(sks)]
    bad = (items[0][0], b"another message", items[0][2])
    assert be.stats()["host_verify"] == {"calls": 0, "items": 0, "s": 0.0}

    if site == "cutover":
        assert be.verify_batch(items[:3], caller=CALLER_INGEST) == [True] * 3
        assert be.verify_batch([items[3], bad], caller=CALLER_CLOSE) == [True, False]
        want_calls, want_items, reasons = 2, 5, ["cutover", "cutover"]
    elif site == "stall-then-latch":
        # the device outlasts its budget, then the class is latched
        assert be.verify_batch(items[:3], caller=CALLER_INGEST) == [True] * 3
        assert be.verify_batch([items[3], bad], caller=CALLER_INGEST) == [True, False]
        assert be.n_latch_flips == {CALLER_INGEST: 1}
        want_calls, want_items, reasons = 2, 5, ["device-stall", "wedge-latch"]
    else:
        encs = [sk.public_raw for sk in sks]
        be.cpu_cutover = 8
        assert be.torsion_check(encs, caller=CALLER_OVERLAY) == [True] * 5  # cutover
        be.cpu_cutover = 0
        assert be.torsion_check(encs, caller=CALLER_OVERLAY) == [True] * 5  # stall
        assert be.torsion_check(encs, caller=CALLER_OVERLAY) == [True] * 5  # latched
        assert be.n_wedge_fallback_items == 10 and be.n_cutover_torsion == 5
        want_calls, want_items, reasons = 0, 0, []

    s = be.stats()
    hv = s["host_verify"]
    assert (hv["calls"], hv["items"]) == (want_calls, want_items)
    assert hv["items"] == sum(v["host"] for v in s["caller_items"].values())
    assert hv["items"] == s["cpu_cutover_items"] + (
        s["wedge_fallback_items"] if site == "stall-then-latch" else 0
    )
    spans = [sp for sp in be._tracer.spans() if sp.name == "sig.host_verify"]
    assert [sp.attrs["reason"] for sp in spans] == reasons
    assert sum(sp.attrs["items"] for sp in spans) == want_items
    if want_items:
        # both clocks are the machine's monotonic one: libsodium's seconds
        # lie inside the spans
        assert 0.0 < hv["s"] <= sum(sp.duration for sp in spans)
    else:
        assert hv["s"] == 0.0
        assert len([sp for sp in be._tracer.spans() if sp.name == "sig.host_torsion"]) == 3


def test_host_verify_counts_survive_concurrent_callers():
    """More callers than cores, a short switch interval: no update of the
    three ``host_verify`` counters is lost."""
    import sys
    import threading

    from stellar_tpu.crypto.sigbackend import CALLER_CLOSE, TpuSigBackend

    be = TpuSigBackend.__new__(TpuSigBackend)  # skip JAX verifier init
    be.cpu_cutover = 8
    be.n_cutover_items = 0
    be._wedge_lock = threading.Lock()
    sk = SecretKey.pseudo_random_for_testing(77)
    items = [(sk.public_raw, b"hv", sk.sign(b"hv"))] * 3
    threads, rounds = 16, 200
    failed = []

    def caller():
        try:
            for _ in range(rounds):
                if be.verify_batch(items, caller=CALLER_CLOSE) != [True] * 3:
                    failed.append("verdict")
        except Exception as e:  # read in the assertion below
            failed.append(repr(e))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=caller) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in ts) and not failed, failed
    assert (be.n_host_verify_calls, be.n_host_verify_items) == (threads * rounds, 3 * threads * rounds)
    assert be.host_verify_s > 0.0


def test_first_dispatch_of_each_bucket_gets_the_compile_budget():
    """The compile-bearing budget follows the compiled SHAPE, not the
    surface: every bucket's first dispatch traces+lowers+compiles inside
    the call and outlasts the short budget.  A healthy device must never
    be abandoned for that — no latch flip, no host items — including a
    flush that spans two new buckets, and a later flush that meets a
    further new bucket after the surface is long warm."""
    import threading
    import time as _time

    from stellar_tpu.crypto.sigbackend import CALLER_CLOSE, TpuSigBackend
    from stellar_tpu.ops.programs import BucketPrograms
    from stellar_tpu.ops.verifier import BatchVerifier

    be = TpuSigBackend.__new__(TpuSigBackend)  # skip JAX verifier init
    be.cpu_cutover = 0
    be.n_cutover_items = 0
    be.n_wedge_fallback_items = 0
    be._wedged_until = {}
    be.n_latch_flips = {}
    be._wedge_lock = threading.Lock()
    be.DEVICE_TIMEOUT = 0.2
    be.DEVICE_FIRST_TIMEOUT = 1.5

    class SlowFirstCompile(BatchVerifier):
        """The real bucket arithmetic and warm-set bookkeeping over a
        kernel stand-in whose first call per bucket takes 3x the short
        budget."""

        def __init__(self):  # no JAX: only the planning state
            self.max_batch = 64
            self.min_device_batch = 16
            self._granule = 1
            self.host_assist = 0.0
            self._programs = BucketPrograms(
                None, rows=128, backend="xla", interpret=False, device_hash=False, lowering={}
            )
            self.dispatched = []

        def verify(self, items):
            for _, count in self._chunks(len(items)):
                bucket = self._bucket(count)
                if self._programs.cold({bucket}):
                    _time.sleep(0.6)
                with self._programs._lock:
                    self._programs._warm_buckets.add(bucket)
                self.dispatched.append(bucket)
            return [True] * len(items)

    bv = be._verifier = SlowFirstCompile()
    item = (b"\x01" * 32, b"m", b"\x02" * 64)
    # 64 + 16: one flush, two new buckets, 1.2 s against a 0.2 s budget
    assert bv.cold_buckets(80) == 2
    assert be.verify_batch([item] * 80, caller=CALLER_CLOSE) == [True] * 80
    assert bv.dispatched == [64, 16]
    assert bv.cold_buckets(80) == 0
    # the surface is warm; a further NEW bucket still gets its budget
    assert bv.cold_buckets(30) == 1
    assert be.verify_batch([item] * 30, caller=CALLER_CLOSE) == [True] * 30
    assert bv.dispatched == [64, 16, 32]
    # warm buckets run under the short budget and make it
    t0 = _time.perf_counter()
    assert be.verify_batch([item] * 80, caller=CALLER_CLOSE) == [True] * 80
    assert _time.perf_counter() - t0 < 0.2
    assert be.n_latch_flips == {}
    assert be.n_wedge_fallback_items == 0
    assert be.n_cutover_items == 0


def test_start_rejects_insane_quorum_set(clock):
    """A validator whose configured QUORUM_SET omits itself must fail fast
    at start (reference: ApplicationImpl.cpp:230-240)."""
    cfg = T.get_test_config(81)
    cfg.QUORUM_SET = X.SCPQuorumSet(
        threshold=1,
        validators=[SecretKey.pseudo_random_for_testing(999).get_public_key()],
        innerSets=[],
    )
    a = Application.create(clock, cfg, new_db=True)
    try:
        with pytest.raises(ValueError, match="QUORUM_SET"):
            a.start()
    finally:
        a.database.close()


def test_start_rejects_zero_threshold_quorum(clock):
    cfg = T.get_test_config(82)
    cfg.QUORUM_SET = X.SCPQuorumSet(threshold=0, validators=[], innerSets=[])
    a = Application.create(clock, cfg, new_db=True)
    try:
        with pytest.raises(ValueError, match="Quorum not configured"):
            a.start()
    finally:
        a.database.close()


class TestMidOpFaultCacheConsistency:
    """Advisor r04 (medium, tx/frame.py): an op that stores an entry and
    then raises a non-rollback exception must not leave the stored value in
    the shared decoded-entry cache — the savepoint rollback undoes the SQL
    row, and the in-flight op_delta's rollback must flush the cache line,
    or later loads in the same close read rolled-back state."""

    def test_cache_flushed_when_op_raises_mid_apply(self, app, root):
        from stellar_tpu.ledger.accountframe import AccountFrame
        from stellar_tpu.ledger.delta import LedgerDelta

        a1 = T.get_account("midopfault")
        fund(app, root, a1)
        pk = a1.get_public_key()
        before = AccountFrame.load_account(pk, app.database).get_balance()
        seq = AccountFrame.load_account(pk, app.database).get_seq_num()

        lm = app.ledger_manager
        tx = T.tx_from_ops(app, a1, seq + 1, [T.payment_op(root, 100)])
        fee = tx.envelope.tx.fee

        def poisoned(op_delta, app_):
            frame = AccountFrame.load_account(pk, app_.database)
            frame.account.balance -= 777
            frame.store_change(op_delta, app_.database)  # cache written NOW
            raise RuntimeError("injected mid-op fault")

        with app.database.transaction():
            delta = LedgerDelta(lm.current.header, app.database)
            tx.process_fee_seq_num(delta, lm)  # reset_results rebuilds ops
            tx.operations[0].apply = poisoned
            with pytest.raises(RuntimeError, match="mid-op fault"):
                tx.apply(delta, app)
            delta.commit()  # fee/seq consumption survives, like the close

        # cache-visible load must equal committed state: fee charged, the
        # -777 mutation gone from BOTH the DB (savepoint) and the cache
        acct = AccountFrame.load_account(pk, app.database)
        assert acct.get_balance() == before - fee
        # prove the DB row agrees with what the cache served
        app.database._entry_cache.clear()
        assert AccountFrame.load_account(pk, app.database).get_balance() == before - fee
