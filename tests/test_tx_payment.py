"""Payment / path-payment corpus (reference: src/transactions/PaymentTests.cpp).

The scenarios test_tx.py does not already pin: send-to-self, the
below-reserve rescue, break-the-second-payment inside a real close,
missing-issuer edges (NO_ISSUER at every path position, change-trust after
issuer merge), issuer-scale INT64_MAX amounts, the authorize-flag
revocation round-trip, and the multi-hop path-payment matrix (sendmax,
cross-self, participant limits, deleted trust lines mid-path).
"""

import pytest

import stellar_tpu.xdr as X
from stellar_tpu.ledger.accountframe import AccountFrame
from stellar_tpu.ledger.delta import LedgerDelta
from stellar_tpu.ledger.offerframe import OfferFrame
from stellar_tpu.ledger.trustframe import TrustFrame
from stellar_tpu.main.application import Application
from stellar_tpu.tx import testutils as T
from stellar_tpu.util import VIRTUAL_TIME, VirtualClock

RC = X.TransactionResultCode
PC = X.PaymentResultCode
PPC = X.PathPaymentResultCode
CTC = X.ChangeTrustResultCode

M = 1_000_000
INT64_MAX = 2**63 - 1
TL_LIMIT = 1_000_000 * M
TL_START = 20_000 * M  # trustLineStartingBalance


@pytest.fixture
def clock():
    c = VirtualClock(VIRTUAL_TIME)
    yield c
    c.shutdown()


@pytest.fixture
def app(clock):
    a = Application(clock, T.get_test_config(), new_db=True)
    yield a
    a.database.close()


@pytest.fixture
def root(app):
    return T.root_key_for(app)


def seq_of(app, key):
    return AccountFrame.load_account(
        key.get_public_key(), app.database
    ).get_seq_num()


def balance_of(app, key):
    return AccountFrame.load_account(
        key.get_public_key(), app.database
    ).get_balance()


def line_balance(app, key, asset):
    line = TrustFrame.load_trust_line(key.get_public_key(), asset,
                                      app.database)
    assert line is not None
    return line.get_balance()


def apply_one(app, source, op_, expect=RC.txSUCCESS):
    tx = T.tx_from_ops(app, source, seq_of(app, source) + 1, [op_])
    T.apply_tx(app, tx, expect_code=expect)
    return tx


def fund(app, root, dest, amount):
    apply_one(app, root, T.create_account_op(dest, amount))
    return dest


def check_amounts(a, b, maxd=1):
    assert b - maxd <= a <= b, f"{a} not in [{b - maxd}, {b}]"


class TestNativePaymentEdges:
    def test_send_to_self(self, app, root):
        """PaymentTests.cpp:149-158 — only the fee leaves."""
        before = balance_of(app, root)
        tx = apply_one(app, root, T.payment_op(root, 5000 * M))
        assert balance_of(app, root) == before - tx.get_fee()

    def test_rescue_account_below_reserve(self, app, root):
        """PaymentTests.cpp:167-191 — a reserve raise strands the account
        (txINSUFFICIENT_BALANCE), a top-up unblocks it."""
        lm = app.ledger_manager
        org_reserve = lm.get_min_balance(0)
        b1 = fund(app, root, T.get_account(1), org_reserve + 1000)
        lm.current.header.baseReserve += 100000

        tx = T.tx_from_ops(app, b1, seq_of(app, b1) + 1,
                           [T.payment_op(root, 1)])
        assert not tx.check_valid(app, 0)
        assert tx.get_result_code() == RC.txINSUFFICIENT_BALANCE

        top_up = lm.get_min_balance(0) - org_reserve
        apply_one(app, root, T.payment_op(b1, top_up))
        apply_one(app, b1, T.payment_op(root, 1))

    def test_two_payments_first_breaking_second(self, app, root):
        """PaymentTests.cpp:192-219 — a real close: tx1 drains b1 so tx2
        fails txINSUFFICIENT_BALANCE; balances follow only tx1+fees."""
        lm = app.ledger_manager
        fee = lm.get_tx_fee()
        payment = lm.current.header.baseReserve * 10
        start = payment + 5 + lm.get_min_balance(0) + fee * 2
        b1 = fund(app, root, T.get_account(1), start)
        seq = seq_of(app, b1)
        tx1 = T.tx_from_ops(app, b1, seq + 1, [T.payment_op(root, payment)])
        tx2 = T.tx_from_ops(app, b1, seq + 2, [T.payment_op(root, 6)])
        root_before = balance_of(app, root)

        from stellar_tpu.herder.txset import TxSetFrame

        txset = TxSetFrame(lm.last_closed.hash, [tx1, tx2])
        txset.sort_for_hash()
        assert txset.check_valid(app)
        T.close_ledger_on(
            app, lm.last_closed.header.scpValue.closeTime + 5, [tx1, tx2]
        )
        assert tx1.get_result_code() == RC.txSUCCESS
        assert tx2.get_result_code() == RC.txINSUFFICIENT_BALANCE
        assert balance_of(app, b1) == lm.get_min_balance(0) + 5
        assert balance_of(app, root) == root_before + payment


@pytest.fixture
def gateways(app, root):
    """gateway (IDR) + gateway2 (USD), a1 trusting both
    (PaymentTests.cpp:58-99 world)."""
    gw = fund(app, root, T.get_account(100), 50_000 * M)
    gw2 = fund(app, root, T.get_account(101), 50_000 * M)
    a1 = fund(app, root, T.get_account(1), 50_000 * M)
    idr = X.Asset.alphanum4(b"IDR", gw.get_public_key())
    usd = X.Asset.alphanum4(b"USD", gw2.get_public_key())
    return gw, gw2, a1, idr, usd


class TestCreditEdges:
    def test_missing_issuer_matrix(self, app, root, gateways):
        """PaymentTests.cpp:268-283 — after the issuer merges away:
        credit to non-issuer fails NO_ISSUER, refunds to the (gone) issuer
        address still work, the limit cannot change, the line can die."""
        gw, gw2, a1, idr, usd = gateways
        apply_one(app, a1, T.change_trust_op(idr, 1000))
        apply_one(app, gw, T.payment_op(a1, 100, asset=idr))
        b1 = fund(app, root, T.get_account(2), 5000 * M)
        apply_one(app, b1, T.change_trust_op(idr, 100))
        # merge the issuer into root
        apply_one(app, gw, T.merge_op(root))
        tx = apply_one(app, a1, T.payment_op(b1, 40, asset=idr),
                       expect=RC.txFAILED)
        assert T.inner_op_code(tx) == PC.PAYMENT_NO_ISSUER
        # refunds to the issuer address burn fine
        apply_one(app, a1, T.payment_op(gw, 75, asset=idr))
        tx = apply_one(app, a1, T.change_trust_op(idr, 25),
                       expect=RC.txFAILED)
        assert T.inner_op_code(tx) == CTC.CHANGE_TRUST_NO_ISSUER
        apply_one(app, a1, T.payment_op(gw, 25, asset=idr))
        apply_one(app, a1, T.change_trust_op(idr, 0))

    def test_issuer_large_amounts(self, app, root, gateways):
        """PaymentTests.cpp:285-303 — INT64_MAX issue and full refund."""
        gw, gw2, a1, idr, usd = gateways
        apply_one(app, a1, T.change_trust_op(idr, INT64_MAX))
        apply_one(app, gw, T.payment_op(a1, INT64_MAX, asset=idr))
        assert line_balance(app, a1, idr) == INT64_MAX
        apply_one(app, a1, T.payment_op(gw, INT64_MAX, asset=idr))
        assert line_balance(app, a1, idr) == 0
        n = app.database.query_one(
            "SELECT COUNT(*) FROM trustlines WHERE accountid = ?",
            (gw.get_strkey_public(),),
        )[0]
        assert n == 0  # the issuer holds no line in its own asset

    def test_authorize_flag_round_trip(self, app, root, gateways):
        """PaymentTests.cpp:304-331 — NOT_AUTHORIZED before allow,
        SRC_NOT_AUTHORIZED after revoke, clean after re-allow."""
        gw, gw2, a1, idr, usd = gateways
        flags = int(X.AccountFlags.AUTH_REQUIRED_FLAG) | int(
            X.AccountFlags.AUTH_REVOCABLE_FLAG)
        apply_one(app, gw, T.set_options_op(set_flags=flags))
        apply_one(app, a1, T.change_trust_op(idr, TL_LIMIT))
        tx = apply_one(app, gw, T.payment_op(a1, TL_START, asset=idr),
                       expect=RC.txFAILED)
        assert T.inner_op_code(tx) == PC.PAYMENT_NOT_AUTHORIZED
        apply_one(app, gw, T.allow_trust_op(a1, b"IDR", True))
        apply_one(app, gw, T.payment_op(a1, TL_START, asset=idr))
        apply_one(app, gw, T.allow_trust_op(a1, b"IDR", False))
        tx = apply_one(app, a1, T.payment_op(gw, TL_START, asset=idr),
                       expect=RC.txFAILED)
        assert T.inner_op_code(tx) == PC.PAYMENT_SRC_NOT_AUTHORIZED
        apply_one(app, gw, T.allow_trust_op(a1, b"IDR", True))
        apply_one(app, a1, T.payment_op(gw, TL_START, asset=idr))


@pytest.fixture
def path_world(app, root, gateways):
    """The order book for the path matrix (PaymentTests.cpp:342-388):
    a1 holds USD(gw2); b1 sells 100 IDR @ 2 USD, c1 sells 100 IDR @ 1.5."""
    gw, gw2, a1, idr, usd = gateways
    apply_one(app, a1, T.change_trust_op(usd, TL_LIMIT))
    apply_one(app, a1, T.change_trust_op(idr, TL_LIMIT))
    apply_one(app, gw2, T.payment_op(a1, TL_START, asset=usd))

    def seller(n):
        s = fund(app, root, T.get_account(n), 5000 * M)
        apply_one(app, s, T.change_trust_op(usd, TL_LIMIT))
        apply_one(app, s, T.change_trust_op(idr, TL_LIMIT))
        apply_one(app, gw, T.payment_op(s, TL_START, asset=idr))
        return s

    b1, c1 = seller(2), seller(3)
    tx = apply_one(
        app, b1, T.manage_offer_op(idr, usd, 100 * M, X.Price(2, 1))
    )
    offer_b = T.op_result_of(tx).value.value.value.offer.value.offerID
    tx = apply_one(
        app, c1, T.manage_offer_op(idr, usd, 100 * M, X.Price(3, 2))
    )
    offer_c = T.op_result_of(tx).value.value.value.offer.value.offerID
    return gw, gw2, a1, b1, c1, idr, usd, offer_b, offer_c


def path_result(tx):
    return T.op_result_of(tx).value.value


class TestPathPayment:
    def test_too_few_offers(self, app, root, gateways):
        """PaymentTests.cpp:335-340 — an empty book cannot source IDR."""
        gw, gw2, a1, idr, usd = gateways
        apply_one(app, a1, T.change_trust_op(idr, TL_LIMIT))
        tx = apply_one(
            app, gw,
            T.path_payment_op(a1, X.Asset.native(), 10_000 * M, idr, 100 * M),
            expect=RC.txFAILED,
        )
        assert T.inner_op_code(tx) == PPC.PATH_PAYMENT_TOO_FEW_OFFERS

    def test_over_sendmax(self, app, root, path_world):
        """PaymentTests.cpp:389-398 ("send with path (over sendmax)")."""
        gw, gw2, a1, b1, c1, idr, usd, ob, oc = path_world
        tx = apply_one(
            app, a1, T.path_payment_op(b1, usd, 149 * M, idr, 100 * M),
            expect=RC.txFAILED,
        )
        assert T.inner_op_code(tx) == PPC.PATH_PAYMENT_OVER_SENDMAX

    def test_success_through_two_offers(self, app, root, path_world):
        """PaymentTests.cpp:399-446 — 125 IDR costs 150 (all of C's offer)
        + 50 (quarter of B's); the result lists both claimed offers."""
        gw, gw2, a1, b1, c1, idr, usd, ob, oc = path_world
        tx = apply_one(
            app, a1, T.path_payment_op(b1, usd, 250 * M, idr, 125 * M)
        )
        multi = path_result(tx).value
        assert [o.offerID for o in multi.offers] == [oc, ob]
        assert OfferFrame.load_offer(
            c1.get_public_key(), oc, app.database) is None
        check_amounts(line_balance(app, c1, idr), TL_START - 100 * M)
        check_amounts(line_balance(app, c1, usd), 150 * M)
        b_res = multi.offers[1]
        assert b_res.sellerID == b1.get_public_key()
        check_amounts(b_res.amountSold, 25 * M)
        offer = OfferFrame.load_offer(b1.get_public_key(), ob, app.database)
        check_amounts(offer.offer.amount, 75 * M)
        check_amounts(line_balance(app, b1, idr),
                      TL_START + (125 - 25) * M)
        check_amounts(line_balance(app, b1, usd), 50 * M)
        check_amounts(line_balance(app, a1, idr), 0)
        check_amounts(line_balance(app, a1, usd), TL_START - 200 * M)

    @pytest.mark.parametrize("position", ["last", "first", "mid"])
    def test_missing_issuer_along_path(self, app, root, path_world,
                                       position):
        """PaymentTests.cpp:450-484 — NO_ISSUER names the dead asset."""
        gw, gw2, a1, b1, c1, idr, usd, ob, oc = path_world
        path = ()
        if position == "last":
            apply_one(app, gw, T.merge_op(root))
            dead = idr
        elif position == "first":
            apply_one(app, gw2, T.merge_op(root))
            dead = usd
        else:
            missing = T.get_account(999)
            dead = X.Asset.alphanum4(b"BTC", missing.get_public_key())
            path = (dead,)
        tx = apply_one(
            app, a1,
            T.path_payment_op(b1, usd, 250 * M, idr, 125 * M, path=path),
            expect=RC.txFAILED,
        )
        assert T.inner_op_code(tx) == PPC.PATH_PAYMENT_NO_ISSUER
        assert path_result(tx).value == dead

    def test_issuer_dest_cannot_take_offers(self, app, root, path_world):
        """PaymentTests.cpp:485-501 — paying the (merged-away) issuer
        through the book reports NO_DESTINATION."""
        gw, gw2, a1, b1, c1, idr, usd, ob, oc = path_world
        apply_one(app, gw, T.merge_op(root))
        tx = apply_one(
            app, a1, T.path_payment_op(gw, usd, 250 * M, idr, 125 * M),
            expect=RC.txFAILED,
        )
        assert T.inner_op_code(tx) == PPC.PATH_PAYMENT_NO_DESTINATION

    def test_takes_own_offer_rejected(self, app, root, path_world):
        """PaymentTests.cpp:502-517 — a path crossing the sender's own
        offer fails OFFER_CROSS_SELF."""
        gw, gw2, a1, b1, c1, idr, usd, ob, oc = path_world
        apply_one(app, root, T.payment_op(a1, 100 * M))
        apply_one(
            app, a1,
            T.manage_offer_op(usd, X.Asset.native(), 100 * M, X.Price(1, 1)),
        )
        tx = apply_one(
            app, a1,
            T.path_payment_op(b1, X.Asset.native(), 100 * M, usd, 100 * M),
            expect=RC.txFAILED,
        )
        assert T.inner_op_code(tx) == PPC.PATH_PAYMENT_OFFER_CROSS_SELF

    def test_offer_participant_reaching_limit(self, app, root, path_world):
        """PaymentTests.cpp:518-569 — C can only receive 120 USD, so its
        100-IDR offer fills 4/5 and is removed."""
        gw, gw2, a1, b1, c1, idr, usd, ob, oc = path_world
        apply_one(app, c1, T.change_trust_op(usd, 120 * M))
        tx = apply_one(
            app, a1, T.path_payment_op(b1, usd, 400 * M, idr, 105 * M)
        )
        multi = path_result(tx).value
        assert [o.offerID for o in multi.offers] == [oc, ob]
        assert OfferFrame.load_offer(
            c1.get_public_key(), oc, app.database) is None
        check_amounts(line_balance(app, c1, idr), TL_START - 80 * M)
        line = TrustFrame.load_trust_line(c1.get_public_key(), usd,
                                          app.database)
        check_amounts(line.get_balance(), line.trust_line.limit)
        b_res = multi.offers[1]
        check_amounts(b_res.amountSold, 25 * M)
        offer = OfferFrame.load_offer(b1.get_public_key(), ob, app.database)
        check_amounts(offer.offer.amount, 75 * M)
        check_amounts(line_balance(app, b1, idr),
                      TL_START + (105 - 25) * M)
        check_amounts(line_balance(app, b1, usd), 50 * M)
        check_amounts(line_balance(app, a1, idr), 0)
        check_amounts(line_balance(app, a1, usd), TL_START - 170 * M)

    @pytest.mark.parametrize("which", ["selling", "buying"])
    def test_deleted_trust_line_invalidates_offer(self, app, root,
                                                  path_world, which):
        """PaymentTests.cpp:570-634 — C's offer is dead weight: claimed
        with amounts 0/0, deleted, and B alone fills the payment."""
        gw, gw2, a1, b1, c1, idr, usd, ob, oc = path_world
        if which == "selling":
            apply_one(app, c1, T.payment_op(gw, TL_START, asset=idr))
            apply_one(app, c1, T.change_trust_op(idr, 0))
        else:
            apply_one(app, c1, T.change_trust_op(usd, 0))
        tx = apply_one(
            app, a1, T.path_payment_op(b1, usd, 200 * M, idr, 25 * M)
        )
        multi = path_result(tx).value
        assert [o.offerID for o in multi.offers] == [oc, ob]
        assert multi.offers[0].amountSold == 0
        assert multi.offers[0].amountBought == 0
        assert OfferFrame.load_offer(
            c1.get_public_key(), oc, app.database) is None
        b_res = multi.offers[1]
        check_amounts(b_res.amountSold, 25 * M)
        offer = OfferFrame.load_offer(b1.get_public_key(), ob, app.database)
        check_amounts(offer.offer.amount, 75 * M)
        # B sold 25 IDR but also RECEIVED the 25 IDR payment: net zero
        check_amounts(line_balance(app, b1, idr), TL_START)
        check_amounts(line_balance(app, b1, usd), 50 * M)
        check_amounts(line_balance(app, a1, idr), 0)
        check_amounts(line_balance(app, a1, usd), TL_START - 50 * M)


# -- PAYMENT against the one-hop PATH_PAYMENT it shares its halves with -----
#
# PAYMENT no longer goes through a PathPaymentOpFrame: both frames call
# ``ops_payment.credit_destination`` / ``debit_source``.  Each case below
# builds the same state on two nodes, applies the PAYMENT on one and the
# PATH_PAYMENT with ``sendMax`` = ``destAmount`` and an empty path on the
# twin, and holds the two to each other.

M_IDR = 1000  # what a funded holder's line starts with


def _twin(instance):
    clock = VirtualClock(VIRTUAL_TIME)
    return clock, Application(clock, T.get_test_config(instance), new_db=True)


def _idr(gw):
    return X.Asset.alphanum4(b"IDR", gw.get_public_key())


def _holders(app, root, auth=False):
    """gateway, a1 holding M_IDR of its IDR, b1 trusting it with room for
    100 more; with `auth` the gateway requires (revocable) authorisation
    and has given it to both."""
    gw = fund(app, root, T.get_account(100), 50_000 * M)
    a1 = fund(app, root, T.get_account(1), 50_000 * M)
    b1 = fund(app, root, T.get_account(2), 50_000 * M)
    idr = _idr(gw)
    if auth:
        flags = int(X.AccountFlags.AUTH_REQUIRED_FLAG) | int(
            X.AccountFlags.AUTH_REVOCABLE_FLAG)
        apply_one(app, gw, T.set_options_op(set_flags=flags))
    apply_one(app, a1, T.change_trust_op(idr, TL_LIMIT))
    apply_one(app, b1, T.change_trust_op(idr, 100))
    if auth:
        apply_one(app, gw, T.allow_trust_op(a1, b"IDR", True))
        apply_one(app, gw, T.allow_trust_op(b1, b"IDR", True))
    apply_one(app, gw, T.payment_op(a1, M_IDR, asset=idr))
    return gw, a1, b1, idr


def _native(amount_of, dest_funded=True, to_self=False):
    def build(app, root):
        a1 = fund(app, root, T.get_account(1), 50_000 * M)
        b1 = T.get_account(2)
        if dest_funded:
            fund(app, root, b1, 50_000 * M)
        lm = app.ledger_manager
        spare = 50_000 * M - lm.get_tx_fee() - lm.get_min_balance(0)
        return a1, (a1 if to_self else b1), None, amount_of(spare)
    return build


def _credit(amount=100, auth=False, then=None, source="a1", dest="b1"):
    """`then(app, root, gw, a1, b1, idr)` bends the world after it is
    built; `source` / `dest` pick the payment's ends from it."""
    def build(app, root):
        gw, a1, b1, idr = _holders(app, root, auth)
        if then is not None:
            then(app, root, gw, a1, b1, idr)
        ends = {"gw": gw, "a1": a1, "b1": b1, "nobody": T.get_account(9)}
        return ends[source], ends[dest], idr, amount
    return build


def _revoke(who):
    def then(app, root, gw, a1, b1, idr):
        apply_one(app, gw, T.allow_trust_op(
            {"a1": a1, "b1": b1}[who], b"IDR", False))
    return then


def _drop_line(who):
    def then(app, root, gw, a1, b1, idr):
        holder = {"a1": a1, "b1": b1}[who]
        if who == "a1":
            apply_one(app, a1, T.payment_op(gw, M_IDR, asset=idr))
        apply_one(app, holder, T.change_trust_op(idr, 0))
    return then


def _merge_issuer(app, root, gw, a1, b1, idr):
    apply_one(app, gw, T.merge_op(root))


# case -> (world, PaymentResultCode, the halves' failure meter or None,
#          whether the twin's state is the payment's)
HALVES = {
    "native-success": (_native(lambda spare: 100 * M), PC.PAYMENT_SUCCESS, None, True),
    # a PATH_PAYMENT to oneself has no early return: it credits a copy of
    # the account and debits the stale signing frame (the reference's own
    # behaviour, test_framecontext's self-path-payment leg), so only the
    # codes are held to each other and the PAYMENT must leave no change
    "native-self": (_native(lambda spare: 100 * M, to_self=True), PC.PAYMENT_SUCCESS, None, False),
    "native-no-destination": (
        _native(lambda spare: 100 * M, dest_funded=False),
        PC.PAYMENT_NO_DESTINATION, "no-destination", True),
    "native-underfunded": (
        _native(lambda spare: 50_000 * M), PC.PAYMENT_UNDERFUNDED, "underfunded", True),
    "native-down-to-the-reserve": (_native(lambda spare: spare), PC.PAYMENT_SUCCESS, None, True),
    "native-one-under-the-reserve": (
        _native(lambda spare: spare + 1), PC.PAYMENT_UNDERFUNDED, "underfunded", True),
    "credit-success": (_credit(), PC.PAYMENT_SUCCESS, None, True),
    "credit-self": (_credit(dest="a1"), PC.PAYMENT_SUCCESS, None, False),
    "credit-no-destination": (
        _credit(dest="nobody"), PC.PAYMENT_NO_DESTINATION, "no-destination", True),
    "credit-underfunded": (
        _credit(then=lambda app, root, gw, a1, b1, idr: apply_one(
            app, a1, T.payment_op(gw, M_IDR - 99, asset=idr))),
        PC.PAYMENT_UNDERFUNDED, "underfunded", True),
    "credit-line-full": (_credit(amount=101), PC.PAYMENT_LINE_FULL, "line-full", True),
    "credit-no-trust": (_credit(then=_drop_line("b1")), PC.PAYMENT_NO_TRUST, "no-trust", True),
    "credit-not-authorized": (
        _credit(auth=True, then=_revoke("b1")),
        PC.PAYMENT_NOT_AUTHORIZED, "not-authorized", True),
    "credit-src-no-trust": (
        _credit(then=_drop_line("a1")), PC.PAYMENT_SRC_NO_TRUST, "src-no-trust", True),
    "credit-src-not-authorized": (
        _credit(auth=True, then=_revoke("a1")),
        PC.PAYMENT_SRC_NOT_AUTHORIZED, "src-not-authorized", True),
    "credit-no-issuer": (_credit(then=_merge_issuer), PC.PAYMENT_NO_ISSUER, "no-issuer", True),
    # the bypass: the issuer as destination is never loaded ...
    "credit-back-to-issuer": (_credit(dest="gw"), PC.PAYMENT_SUCCESS, None, True),
    # ... so credit can be burnt at an issuer that no longer exists
    "credit-back-to-merged-issuer": (
        _credit(then=_merge_issuer, dest="gw"), PC.PAYMENT_SUCCESS, None, True),
    "credit-issuer-pays-out": (_credit(source="gw", dest="b1"), PC.PAYMENT_SUCCESS, None, True),
    "credit-issuer-pays-out-line-full": (
        _credit(amount=101, source="gw", dest="b1"), PC.PAYMENT_LINE_FULL, "line-full", True),
}


def _apply_recording(app, source, op_):
    """One iteration of the close's loop (fee, then apply), keeping what a
    close would hand on: the meta, and the delta's live and dead entries
    and header — the inputs of the bucket list and so of the ledger hash."""
    lm = app.ledger_manager
    tx = T.tx_from_ops(app, source, seq_of(app, source) + 1, [op_])
    meta = X.TransactionMeta(0, [])
    with app.database.transaction():
        delta = LedgerDelta(lm.current.header, app.database)
        tx.process_fee_seq_num(delta, lm)
        tx.apply(delta, app, meta)
        handed_on = (
            meta.to_xdr(),
            sorted(e.to_xdr() for e in delta.get_live_entries()),
            sorted(k.to_xdr() for k in delta.get_dead_entries()),
            delta.get_header().to_xdr(),
        )
        delta.commit()
    return tx, handed_on


def _meter(app, *parts):
    return app.metrics.new_meter(parts, "operation").count


@pytest.mark.parametrize("case", sorted(HALVES))
def test_payment_equals_one_hop_path_payment(case):
    from stellar_tpu.tx.ops_payment import _PP_TO_PAYMENT

    world, want, failure_meter, same_state = HALVES[case]
    clock_a, pay_app = _twin(0)
    clock_b, path_app = _twin(1)
    try:
        built = []
        for app in (pay_app, path_app):
            built.append(world(app, T.root_key_for(app)))
            assert T.dump_state(app.database) == T.dump_state(pay_app.database)
        source, dest, asset, amount = built[0]
        assert [k.get_public_key() for k in built[1][:2]] == [
            source.get_public_key(), dest.get_public_key()]
        native = X.Asset.native()
        before = T.dump_state(pay_app.database)
        balance_before = balance_of(pay_app, source)

        pay_tx, pay_handed = _apply_recording(
            pay_app, source, T.payment_op(dest, amount, asset=asset))
        path_tx, path_handed = _apply_recording(
            path_app, source,
            T.path_payment_op(dest, asset or native, amount, asset or native, amount))

        # the outcome is the case's, and the two agree on it
        assert T.inner_op_code(pay_tx) == want
        assert pay_tx.get_result_code() == path_tx.get_result_code()
        path_code = T.inner_op_code(path_tx)
        if want == PC.PAYMENT_SUCCESS:
            assert pay_tx.get_result_code() == RC.txSUCCESS
            assert path_code == PPC.PATH_PAYMENT_SUCCESS
            last = T.op_result_of(path_tx).value.value.value.last
            assert (last.destination, last.amount) == (dest.get_public_key(), amount)
        else:
            assert pay_tx.get_result_code() == RC.txFAILED
            assert _PP_TO_PAYMENT[path_code] == want
            if want == PC.PAYMENT_NO_ISSUER:
                assert T.op_result_of(path_tx).value.value.value == asset

        # the halves' meters keep their names on both nodes; the success
        # meter of the path payment counts path payments alone
        if failure_meter is not None:
            for app in (pay_app, path_app):
                assert _meter(app, "op-path-payment", "failure", failure_meter) == 1
        ok = int(want == PC.PAYMENT_SUCCESS)
        assert _meter(path_app, "op-path-payment", "success", "apply") == ok
        # (the payments that built the world raise it no more either)
        assert _meter(pay_app, "op-path-payment", "success", "apply") == 0

        if same_state:
            assert pay_handed == path_handed
            assert T.dump_state(pay_app.database) == T.dump_state(path_app.database)
        else:
            # the payment to oneself: only the fee and the sequence number
            meta_xdr, live, dead, _header = pay_handed
            assert meta_xdr == X.TransactionMeta(0, [X.OperationMeta([])]).to_xdr()
            assert len(live) == 1 and not dead
            assert T.dump_state(pay_app.database)["trustlines"] == before["trustlines"]
            assert balance_of(pay_app, source) == balance_before - pay_tx.get_fee()
    finally:
        for clock, app in ((clock_a, pay_app), (clock_b, path_app)):
            app.database.close()
            clock.shutdown()
