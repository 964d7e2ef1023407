"""Payment and PathPayment operations (reference:
src/transactions/PaymentOpFrame.cpp, PathPaymentOpFrame.cpp).

A payment is a path payment with nothing to convert: the two operations
share the halves around the walk over the book — ``credit_destination`` and
``debit_source`` below — and each frame builds only its own result around
them.  (The reference shares the same code by having PaymentOpFrame build a
one-hop PathPaymentOp and translate its result back.)
"""

from __future__ import annotations

from ..ledger.accountframe import AccountFrame
from ..ledger.trustframe import TrustFrame
from ..util.xmath import INT64_MAX
from ..xdr.txs import (
    PathPaymentResult,
    PathPaymentResultCode,
    PathPaymentSuccess,
    PaymentResult,
    PaymentResultCode,
    SimplePaymentResult,
)
from .offerexchange import ConvertResult, OfferExchange, OfferFilterResult
from .opframe import OperationFrame, is_asset_valid

_SUCCESS = PathPaymentResultCode.PATH_PAYMENT_SUCCESS

_PP_TO_PAYMENT = {
    PathPaymentResultCode.PATH_PAYMENT_UNDERFUNDED: PaymentResultCode.PAYMENT_UNDERFUNDED,
    PathPaymentResultCode.PATH_PAYMENT_SRC_NOT_AUTHORIZED: PaymentResultCode.PAYMENT_SRC_NOT_AUTHORIZED,
    PathPaymentResultCode.PATH_PAYMENT_SRC_NO_TRUST: PaymentResultCode.PAYMENT_SRC_NO_TRUST,
    PathPaymentResultCode.PATH_PAYMENT_NO_DESTINATION: PaymentResultCode.PAYMENT_NO_DESTINATION,
    PathPaymentResultCode.PATH_PAYMENT_NO_TRUST: PaymentResultCode.PAYMENT_NO_TRUST,
    PathPaymentResultCode.PATH_PAYMENT_NOT_AUTHORIZED: PaymentResultCode.PAYMENT_NOT_AUTHORIZED,
    PathPaymentResultCode.PATH_PAYMENT_LINE_FULL: PaymentResultCode.PAYMENT_LINE_FULL,
    PathPaymentResultCode.PATH_PAYMENT_NO_ISSUER: PaymentResultCode.PAYMENT_NO_ISSUER,
}


def _pays_issuer(asset, destination) -> bool:
    """send-credits-back-to-issuer: the destination of a direct single-asset
    payment need not exist (nor the issuer be looked up) when it IS the
    asset's issuer."""
    return not asset.is_native() and asset.code_and_issuer()[1] == destination


def _stopped(metrics, tag, code):
    metrics.new_meter(("op-path-payment", "failure", tag), "operation").mark()
    return code


def credit_destination(metrics, delta, db, destination_id, asset, amount, bypass_issuer_check):
    """The first half of a payment: `amount` of `asset` reaches the
    destination, stored through `delta`.  -> PATH_PAYMENT_SUCCESS, or the
    code it stopped at with nothing stored (NO_ISSUER is about `asset`)."""
    destination = None
    if not bypass_issuer_check:
        destination = AccountFrame.load_account(destination_id, db)
        if destination is None:
            return _stopped(
                metrics, "no-destination", PathPaymentResultCode.PATH_PAYMENT_NO_DESTINATION
            )

    if asset.is_native():
        destination.mut().balance += amount
        destination.store_change(delta, db)
        return _SUCCESS

    if bypass_issuer_check:
        dest_line = TrustFrame.load_trust_line(destination_id, asset, db)
    else:
        dest_line, issuer = TrustFrame.load_trust_line_issuer(destination_id, asset, db)
        if issuer is None:
            return _stopped(metrics, "no-issuer", PathPaymentResultCode.PATH_PAYMENT_NO_ISSUER)
    if dest_line is None:
        return _stopped(metrics, "no-trust", PathPaymentResultCode.PATH_PAYMENT_NO_TRUST)
    if not dest_line.is_authorized():
        return _stopped(
            metrics, "not-authorized", PathPaymentResultCode.PATH_PAYMENT_NOT_AUTHORIZED
        )
    if not dest_line.add_balance(amount):
        return _stopped(metrics, "line-full", PathPaymentResultCode.PATH_PAYMENT_LINE_FULL)
    dest_line.store_change(delta, db)
    return _SUCCESS


def debit_source(metrics, delta, lm, source_account, asset, amount, bypass_issuer_check):
    """The last half: `amount` of `asset` leaves the source, stored through
    `delta`.  -> PATH_PAYMENT_SUCCESS, or the code it stopped at with
    nothing stored (NO_ISSUER is about `asset`)."""
    db = lm.database
    if asset.is_native():
        min_balance = source_account.get_minimum_balance(lm)
        if source_account.get_balance() - amount < min_balance:
            return _stopped(metrics, "underfunded", PathPaymentResultCode.PATH_PAYMENT_UNDERFUNDED)
        source_account.mut().balance -= amount
        source_account.store_change(delta, db)
        return _SUCCESS

    source_id = source_account.get_id()
    if bypass_issuer_check:
        source_line = TrustFrame.load_trust_line(source_id, asset, db)
    else:
        source_line, issuer = TrustFrame.load_trust_line_issuer(source_id, asset, db)
        if issuer is None:
            return _stopped(metrics, "no-issuer", PathPaymentResultCode.PATH_PAYMENT_NO_ISSUER)
    if source_line is None:
        return _stopped(metrics, "src-no-trust", PathPaymentResultCode.PATH_PAYMENT_SRC_NO_TRUST)
    if not source_line.is_authorized():
        return _stopped(
            metrics, "src-not-authorized", PathPaymentResultCode.PATH_PAYMENT_SRC_NOT_AUTHORIZED
        )
    if not source_line.add_balance(-amount):
        return _stopped(metrics, "underfunded", PathPaymentResultCode.PATH_PAYMENT_UNDERFUNDED)
    source_line.store_change(delta, db)
    return _SUCCESS


class PaymentOpFrame(OperationFrame):
    @property
    def payment(self):
        return self.operation.body.value

    def do_check_valid(self, metrics) -> bool:
        if self.payment.amount <= 0:
            metrics.new_meter(
                ("op-payment", "invalid", "malformed-negative-amount"), "operation"
            ).mark()
            self.set_inner_result(PaymentResult(PaymentResultCode.PAYMENT_MALFORMED))
            return False
        if not is_asset_valid(self.payment.asset):
            metrics.new_meter(
                ("op-payment", "invalid", "malformed-invalid-asset"), "operation"
            ).mark()
            self.set_inner_result(PaymentResult(PaymentResultCode.PAYMENT_MALFORMED))
            return False
        return True

    def do_apply(self, metrics, delta, lm) -> bool:
        payment = self.payment
        if payment.destination == self.get_source_id():
            metrics.new_meter(("op-payment", "success", "apply"), "operation").mark()
            self.set_inner_result(PaymentResult(PaymentResultCode.PAYMENT_SUCCESS))
            return True

        # from here on the payment has a body: the two halves it shares with
        # PATH_PAYMENT, destination first, so a source that cannot pay
        # unwinds the destination's store through the operation's delta
        lm.exchange_stats["payments_applied"] += 1
        asset = payment.asset
        bypass_issuer_check = _pays_issuer(asset, payment.destination)
        code = credit_destination(
            metrics, delta, lm.database, payment.destination, asset, payment.amount,
            bypass_issuer_check,
        )
        if code == _SUCCESS:
            code = debit_source(
                metrics, delta, lm, self.source_account, asset, payment.amount,
                bypass_issuer_check,
            )
        if code != _SUCCESS:
            self.set_inner_result(PaymentResult(_PP_TO_PAYMENT[code]))
            return False

        metrics.new_meter(("op-payment", "success", "apply"), "operation").mark()
        self.set_inner_result(PaymentResult(PaymentResultCode.PAYMENT_SUCCESS))
        return True


class PathPaymentOpFrame(OperationFrame):
    @property
    def pp(self):
        return self.operation.body.value

    def _stop(self, code, no_issuer_asset=None):
        if code == PathPaymentResultCode.PATH_PAYMENT_NO_ISSUER:
            self.set_inner_result(PathPaymentResult(code, no_issuer_asset))
        else:
            self.set_inner_result(PathPaymentResult(code))
        return False

    def _fail(self, metrics, tag, code, no_issuer_asset=None):
        return self._stop(_stopped(metrics, tag, code), no_issuer_asset)

    def do_check_valid(self, metrics) -> bool:
        pp = self.pp
        if pp.destAmount <= 0 or pp.sendMax <= 0:
            metrics.new_meter(
                ("op-path-payment", "invalid", "malformed-amounts"), "operation"
            ).mark()
            self.set_inner_result(
                PathPaymentResult(PathPaymentResultCode.PATH_PAYMENT_MALFORMED)
            )
            return False
        if not is_asset_valid(pp.sendAsset) or not is_asset_valid(pp.destAsset) or not all(
            is_asset_valid(a) for a in pp.path
        ):
            metrics.new_meter(
                ("op-path-payment", "invalid", "malformed-currencies"), "operation"
            ).mark()
            self.set_inner_result(
                PathPaymentResult(PathPaymentResultCode.PATH_PAYMENT_MALFORMED)
            )
            return False
        return True

    def do_apply(self, metrics, delta, lm) -> bool:
        db = lm.database
        pp = self.pp

        success = PathPaymentSuccess([], None)
        self.set_inner_result(
            PathPaymentResult(PathPaymentResultCode.PATH_PAYMENT_SUCCESS, success)
        )

        cur_b_received = pp.destAmount
        cur_b = pp.destAsset
        full_path = [pp.sendAsset] + list(pp.path)

        bypass_issuer_check = (
            len(full_path) == 1
            and pp.sendAsset == pp.destAsset
            and _pays_issuer(cur_b, pp.destination)
        )

        # credit the last hop
        code = credit_destination(
            metrics, delta, db, pp.destination, cur_b, cur_b_received, bypass_issuer_check
        )
        if code != _SUCCESS:
            return self._stop(code, cur_b)

        success.last = SimplePaymentResult(pp.destination, cur_b, cur_b_received)

        # walk the path backwards converting through the book
        for cur_a in reversed(full_path):
            if cur_a == cur_b:
                continue
            if not cur_a.is_native():
                if (
                    AccountFrame.load_account(
                        cur_a.code_and_issuer()[1], db, readonly=True
                    )
                    is None
                ):
                    return self._fail(
                        metrics,
                        "no-issuer",
                        PathPaymentResultCode.PATH_PAYMENT_NO_ISSUER,
                        cur_a,
                    )

            oe = OfferExchange(delta, lm)
            stop_code = []

            def offer_filter(o):
                if o.get_seller_id() == self.get_source_id():
                    metrics.new_meter(
                        ("op-path-payment", "failure", "offer-cross-self"), "operation"
                    ).mark()
                    stop_code.append(
                        PathPaymentResultCode.PATH_PAYMENT_OFFER_CROSS_SELF
                    )
                    return OfferFilterResult.STOP
                return OfferFilterResult.KEEP

            r, cur_a_sent, actual_b_received = oe.convert_with_offers(
                cur_a, INT64_MAX, cur_b, cur_b_received, offer_filter
            )
            if r == ConvertResult.FILTER_STOP:
                self.set_inner_result(PathPaymentResult(stop_code[0]))
                return False
            if r == ConvertResult.OK and cur_b_received == actual_b_received:
                pass
            else:
                return self._fail(
                    metrics,
                    "too-few-offers",
                    PathPaymentResultCode.PATH_PAYMENT_TOO_FEW_OFFERS,
                )

            cur_b_received = cur_a_sent
            cur_b = cur_a
            success.offers = oe.offer_trail + success.offers

        # finally: debit the source
        cur_b_sent = cur_b_received
        if cur_b_sent > pp.sendMax:
            return self._fail(
                metrics, "over-send-max", PathPaymentResultCode.PATH_PAYMENT_OVER_SENDMAX
            )

        code = debit_source(
            metrics, delta, lm, self.source_account, cur_b, cur_b_sent, bypass_issuer_check
        )
        if code != _SUCCESS:
            return self._stop(code, cur_b)

        metrics.new_meter(("op-path-payment", "success", "apply"), "operation").mark()
        return True
