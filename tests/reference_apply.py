"""A reference apply by plain arithmetic (ROADMAP A11).

It shares nothing with ``stellar_tpu/ledger`` or ``stellar_tpu/tx``: plain
integers, dicts and sorted lists.  A ledger is

- ``accounts``   ``{account: [balance, seq]}``, with beside it ``subentries``
  ``{account: count}``, ``signers`` ``{account: {key: weight}}``,
  ``thresholds`` ``{account: (master, low, medium, high)}`` and ``flags``;
- ``trustlines`` ``{(account, asset): [balance, limit, authorised]}``;
- ``offers``     ``{offer id: (seller, selling, buying, amount, n, d)}``;
- a fee pool and an id pool.

An asset is ``None`` (native) or ``(code, issuer)``.  A transaction is a
source, a sequence number, a fee, the keys that signed it, and operations:

    ("pay", destination, amount[, asset])                  PAYMENT
    ("create", destination, starting balance)              CREATE_ACCOUNT
    ("path", destination, send asset, send max,
             dest asset, dest amount, (path assets...))    PATH_PAYMENT
    ("offer", selling, buying, amount, (n, d), offer id)   MANAGE_OFFER
    ("trust", asset, limit)                                CHANGE_TRUST
    ("options", ((field, value), ...))                     SET_OPTIONS

The rules are the protocol's as the 2015 source states them
(stellar-core ``TransactionFrame.cpp``, ``PaymentOpFrame.cpp``,
``PathPaymentOpFrame.cpp``, ``ManageOfferOpFrame.cpp``, ``OfferExchange.cpp``,
``ChangeTrustOpFrame.cpp``, ``SetOptionsOpFrame.cpp``,
``CreateAccountOpFrame.cpp``):

**A close** charges every transaction's fee (all the account has, where it
has less) and takes its sequence number, in apply order, before any
transaction is applied.

**A transaction** at apply: a fee under the base fee an operation is
``txINSUFFICIENT_FEE``; signatures that do not reach the source's low
threshold are ``txBAD_AUTH``; a source that could not pay the fee once more
above its reserve — ``(2 + sub-entries) x base reserve`` — is
``txINSUFFICIENT_BALANCE``.  Otherwise its operations apply in order, every
one of them even after one has failed (what a failed operation had already
done stays in sight of the ones after it); where one failed the transaction is
``txFAILED`` and none of its operations' effects stay (the fee and the
sequence number do).  Where all succeeded and a signature was never needed
it is ``txBAD_AUTH_EXTRA``, effects undone.

**Signatures** (``checkSignature``): the keys are the master key, if its
weight is not 0, and the signers; the envelope's signatures are walked in
order, one that is a listed key's adds that key's weight and takes the key
off the list, and the check holds as soon as the sum reaches the needed
weight — so with a needed weight of 0 one good signature is still required.
An operation needs the medium threshold; SET_OPTIONS that touches a weight,
a threshold or a signer needs the high one; short of it: ``opBAD_AUTH``.

**Reserve and sub-entries.**  A trustline, an offer and a signer are one
sub-entry each.  Adding one fails (``..._LOW_RESERVE``) where the balance is
under ``(2 + sub-entries + 1) x base reserve``.

**PAYMENT** of amount <= 0 is ``PAYMENT_MALFORMED``; to oneself it succeeds
and moves nothing; otherwise it is the PATH_PAYMENT with both assets the
same, an empty path and ``sendMax = destAmount``, its codes renamed.

**PATH_PAYMENT**, in the source's order: the destination must exist
(``NO_DESTINATION``) unless it is the issuer of the one asset sent straight
back to it; the destination is credited first — native onto the balance;
credit needs the issuer (``NO_ISSUER``), a line (``NO_TRUST``), authorised
(``NOT_AUTHORIZED``), with room under its limit (``LINE_FULL``); an
issuer's own line takes and gives anything.  Then the path is walked
backwards from the destination asset: each step whose asset differs
converts through the book (below) with no bound on what is sent, any
resting offer of the source's own stops it (``OFFER_CROSS_SELF``), and a
step that delivers less than the amount wanted is ``TOO_FEW_OFFERS``.
Last the source is debited: more than ``sendMax`` is ``OVER_SENDMAX``;
native must leave the reserve (``UNDERFUNDED``); credit needs issuer, line
(``SRC_NO_TRUST``), authorised (``SRC_NOT_AUTHORIZED``), balance
(``UNDERFUNDED``).

**The exchange** (``OfferExchange.cpp``).  The taker sends *sheep* and
receives *wheat* from resting offers that sell wheat for sheep at ``n/d``
sheep a wheat, cheapest first: **by price, then by offer id**.  Crossing
one offer:

1. what the seller can be paid: unbounded in native, else the room on its
   sheep line (none, or not authorised: 0), turned into wheat at
   ``floor(room x d / n)``;
2. what the seller can deliver: native above its reserve, else its wheat
   line's balance (authorised), never below 0;
3. the smaller of the two; where it is under the offer's amount the offer
   shrinks to it, else the offer's amount; then bounded by the wheat the
   taker still wants;
4. ``sheep = floor(wheat x n / d)``, bounded by the sheep the taker may
   still send; then, **towards the seller**, ``wheat = floor(sheep x d /
   n)``.  Both roundings are floors: at a price that does not divide the
   amount the taker is delivered *less* than asked (10 wheat at 101/100:
   10 sheep, then 9 wheat), which makes the offer a partial fill and ends
   the walk — a path payment of such an amount is ``TOO_FEW_OFFERS``
   however deep the book.  That is the 2015 protocol's arithmetic, not a
   fault of a program that follows it (the issue's probe met it; protocol
   10 replaced it).
5. where wheat or sheep came to 0: if a bound of the taker's cut it, the
   walk ends with nothing more converted; else the offer is worthless and
   is deleted, nothing moving;
6. the offer is taken where its amount is not above the wheat delivered:
   deleted, the seller one sub-entry fewer; else reduced by it.  The seller
   is credited the sheep and debited the wheat.

The walk ends when the taker's wheat or sheep is used up, after a partial
fill, or at the book's end.  The source reads the book five offers a page;
a plain walk over the sorted book is the same thing.

**MANAGE_OFFER** sells *sheep* (selling) for *wheat* (buying) at ``n/d``
wheat a sheep.  Equal assets, a negative amount or a price part <= 0 is
``MALFORMED``.  Unless the amount is 0: a credit sold needs its issuer
(``SELL_NO_ISSUER``), a line (``SELL_NO_TRUST``) that is not empty
(``UNDERFUNDED``) and authorised (``SELL_NOT_AUTHORIZED``); a credit bought
needs issuer, line, authorised (``BUY_...``).  An offer id other than 0
names an offer of the source's (``NOT_FOUND``), which takes the
operation's assets, amount and price.  Amount 0 deletes.  Otherwise what may
be sold is the amount, bounded by what the source holds (native above the
reserve, or the line's balance) and by ``floor(room to receive x d / n)``
(a full wheat line is ``LINE_FULL``); the offer crosses the book of offers
selling wheat for sheep while their price is at most ``d/n``, skipping
itself; an offer of the source's own within that price is ``CROSS_SELF``.
The source is credited the wheat and debited the sheep; what is left of
the amount rests: a new offer takes the next id of the pool and one
sub-entry (``LOW_RESERVE`` against the balance after the crossing); an
updated one is rewritten; nothing left deletes an existing offer.  A
failure undoes the crossings.

**CHANGE_TRUST**: a negative limit or the native asset is ``MALFORMED``.
On a line that exists: a limit under its balance is ``INVALID_LIMIT``
(so a line that holds credit cannot be deleted); 0 deletes it, one
sub-entry fewer; else the issuer must exist (``NO_ISSUER``) and the limit
is set.  With no line: limit 0 is ``INVALID_LIMIT``, the issuer must exist,
the line is authorised unless the issuer requires authorisation, and takes
a sub-entry (``LOW_RESERVE``).  An issuer's line in its own asset is
unbounded and never stored.

**SET_OPTIONS**: flag bits outside 0x7 are ``UNKNOWN_FLAG``, a bit both
set and cleared ``BAD_FLAGS``, a weight or threshold over 255
``THRESHOLD_OUT_OF_RANGE``, the source as its own signer ``BAD_SIGNER``.
Flags cannot change once AUTH_IMMUTABLE is set (``CANT_CHANGE``).  A
signer of weight 0 is removed (one sub-entry fewer); a known one has its
weight set; a new one is added up to 20 (``TOO_MANY_SIGNERS``) and takes a
sub-entry (``LOW_RESERVE``).

**CREATE_ACCOUNT**: not its creator and a positive balance
(``MALFORMED``), must not exist (``ALREADY_EXIST``), starts with at least
the reserve of an account with no sub-entries (``LOW_RESERVE``), leaves the
source its reserve (``UNDERFUNDED``), and starts at sequence number
``ledger_seq << 32``.

**At admission** a transaction is refused whose sequence number is not its
account's next (``txBAD_SEQ``), whose source does not exist
(``txNO_ACCOUNT``) or whose fee is under the base fee an operation
(``txINSUFFICIENT_FEE``).

Departures from the source, each deliberate: every operation runs from the
transaction's source (no per-operation source accounts); time bounds,
``inflationDest``, ``homeDomain``, passive offers, ALLOW_TRUST, ACCOUNT_MERGE
and INFLATION are not modelled; asset codes are not checked for form; the
book is ordered by the exact ratio ``n/d`` where the source orders by the
stored double (equal wherever two prices differ by more than a double's
rounding); the source's "offer claimed over limit" faults, which its own
bounds make unreachable, raise here.

The apply order of a set is an input: consensus fixes it (``TxSetFrame
.sort_for_apply``), the apply path only follows it."""

from __future__ import annotations

from dataclasses import dataclass
from fractions import Fraction
from typing import Dict, List, Optional, Sequence, Tuple

INT64_MAX = 2**63 - 1
AUTH_REQUIRED, AUTH_IMMUTABLE, ALL_FLAGS = 0x1, 0x4, 0x7
MAX_SIGNERS = 20
_ABSENT = object()


@dataclass(frozen=True)
class Tx:
    source: str
    seq: int
    fee: int
    ops: Tuple[tuple, ...]
    signed_by: Optional[Tuple[str, ...]] = None  # None: the source's master key alone


class _Fail(Exception):
    """An operation's failure code, raised from wherever it is found."""


def _mul_div(a: int, b: int, c: int) -> int:
    """floor(a x b / c), INT64_MAX where it does not fit (bigDivide)."""
    return min(a * b // c, INT64_MAX)


class Ledger:
    def __init__(self, accounts: Dict[str, Sequence[int]], base_fee: int, base_reserve: int,
                 fee_pool: int = 0, id_pool: int = 0):
        self.accounts = {name: [balance, seq] for name, (balance, seq) in accounts.items()}
        self.subentries: Dict[str, int] = {}
        self.signers: Dict[str, Dict[str, int]] = {}
        self.thresholds: Dict[str, Tuple[int, int, int, int]] = {}
        self.flags: Dict[str, int] = {}
        self.trustlines: Dict[tuple, list] = {}
        self.offers: Dict[int, tuple] = {}
        self.base_fee = base_fee
        self.base_reserve = base_reserve
        self.fee_pool = fee_pool
        self.id_pool = id_pool
        self.claimed = 0  # offers taken or reduced, ever
        self._journal: list = []

    # -- state, every write journalled so that a failure can be undone ----------
    def _put(self, table: dict, key, value) -> None:
        self._journal.append((table, key, table.get(key, _ABSENT)))
        if value is None:
            del table[key]
        else:
            table[key] = value

    def _undo(self, mark: int) -> None:
        while len(self._journal) > mark:
            table, key, old = self._journal.pop()
            if table is None:
                self.id_pool = old
            elif old is _ABSENT:
                table.pop(key, None)
            else:
                table[key] = old

    def min_balance(self, account: str, more: int = 0) -> int:
        return (2 + self.subentries.get(account, 0) + more) * self.base_reserve

    def _balance(self, account: str) -> int:
        return self.accounts[account][0]

    def _move(self, account: str, amount: int) -> None:
        balance, seq = self.accounts[account]
        self._put(self.accounts, account, [balance + amount, seq])

    def _sub_entry(self, account: str, count: int) -> bool:
        if count > 0 and self._balance(account) < self.min_balance(account, count):
            return False
        self._put(self.subentries, account, self.subentries.get(account, 0) + count)
        return True

    def _line(self, account: str, asset) -> Optional[list]:
        """The account's line in ``asset``: an issuer's own is unbounded."""
        if account == asset[1]:
            return [INT64_MAX, INT64_MAX, True]
        return self.trustlines.get((account, asset))

    def _line_add(self, account: str, asset, amount: int) -> bool:
        if account == asset[1] or amount == 0:
            return True
        balance, limit, authorised = self.trustlines[(account, asset)]
        if not authorised or balance + amount > limit or balance + amount < 0:
            return False
        self._put(self.trustlines, (account, asset), [balance + amount, limit, authorised])
        return True

    @staticmethod
    def _room(line: Optional[list]) -> int:
        if line is None or not line[2]:
            return 0
        return line[1] - line[0]

    # -- admission and close ------------------------------------------------------
    def admit(self, tx: Tx) -> str:
        """The code a node's front door gives the transaction, alone."""
        if tx.fee < self.base_fee * len(tx.ops):
            return "txINSUFFICIENT_FEE"
        if tx.source not in self.accounts:
            return "txNO_ACCOUNT"
        balance, seq = self.accounts[tx.source]
        if seq + 1 != tx.seq:
            return "txBAD_SEQ"
        if balance - tx.fee < self.min_balance(tx.source):
            return "txINSUFFICIENT_BALANCE"
        return "txSUCCESS"

    def close(self, ledger_seq: int, txs: Sequence[Tx]) -> List[Tuple[str, List[str]]]:
        """Apply ``txs``, given in apply order -> (code, operation codes) of
        each; operation codes only where the operations ran."""
        for tx in txs:
            balance, seq = self.accounts[tx.source]
            if seq + 1 != tx.seq:
                raise ValueError(f"{tx.source}: sequence {tx.seq} after {seq}")
            fee = min(tx.fee, balance)
            self.accounts[tx.source] = [balance - fee, tx.seq]
            self.fee_pool += fee
        return [self._apply(ledger_seq, tx) for tx in txs]

    # -- signatures -------------------------------------------------------------------
    def _signed(self, tx: Tx, used: list, needed: int) -> bool:
        source = tx.source
        keys = dict(self.signers.get(source, {}))
        master = self.thresholds.get(source, (1, 0, 0, 0))[0]
        if master:
            keys[source] = master
        total = 0
        for i, key in enumerate(tx.signed_by if tx.signed_by is not None else (source,)):
            if key in keys:
                used[i] = True
                total += keys.pop(key)
                if total >= needed:
                    return True
        return False

    def _apply(self, ledger_seq: int, tx: Tx) -> Tuple[str, List[str]]:
        if tx.fee < self.base_fee * len(tx.ops):
            return "txINSUFFICIENT_FEE", []
        used = [False] * (1 if tx.signed_by is None else len(tx.signed_by))
        if not self._signed(tx, used, self.thresholds.get(tx.source, (1, 0, 0, 0))[1]):
            return "txBAD_AUTH", []
        if self._balance(tx.source) - tx.fee < self.min_balance(tx.source):
            return "txINSUFFICIENT_BALANCE", []
        mark = len(self._journal)
        codes = [self._operation(ledger_seq, tx, used, op) for op in tx.ops]
        if not all(code.endswith("_SUCCESS") for code in codes):
            self._undo(mark)
            return "txFAILED", codes
        if not all(used):
            self._undo(mark)
            return "txBAD_AUTH_EXTRA", []
        del self._journal[mark:]
        return "txSUCCESS", codes

    def _operation(self, ledger_seq: int, tx: Tx, used: list, op: tuple) -> str:
        kind = op[0]
        level = 2  # the medium threshold
        if kind == "options" and any(f != "setFlags" and f != "clearFlags" for f, _v in op[1]):
            level = 3
        if not self._signed(tx, used, self.thresholds.get(tx.source, (1, 0, 0, 0))[level]):
            return "opBAD_AUTH"
        try:
            if kind == "pay":
                return self._payment(tx.source, *op[1:])
            if kind == "create":
                return self._create(ledger_seq, tx.source, *op[1:])
            if kind == "path":
                self._path_payment(tx.source, *op[1:])
                return "PATH_PAYMENT_SUCCESS"
            if kind == "offer":
                self._manage_offer(tx.source, *op[1:])
                return "MANAGE_OFFER_SUCCESS"
            if kind == "trust":
                self._change_trust(tx.source, *op[1:])
                return "CHANGE_TRUST_SUCCESS"
            if kind == "options":
                self._set_options(tx.source, dict(op[1]))
                return "SET_OPTIONS_SUCCESS"
        except _Fail as failure:
            return failure.args[0]
        raise AssertionError(kind)

    # -- CREATE_ACCOUNT ---------------------------------------------------------------
    def _create(self, ledger_seq: int, source: str, dest: str, amount: int) -> str:
        if amount <= 0 or dest == source:
            return "CREATE_ACCOUNT_MALFORMED"
        if dest in self.accounts:
            return "CREATE_ACCOUNT_ALREADY_EXIST"
        if amount < 2 * self.base_reserve:
            return "CREATE_ACCOUNT_LOW_RESERVE"
        if self._balance(source) - self.min_balance(source) < amount:
            return "CREATE_ACCOUNT_UNDERFUNDED"
        self._move(source, -amount)
        self._put(self.accounts, dest, [amount, ledger_seq << 32])
        return "CREATE_ACCOUNT_SUCCESS"

    # -- PAYMENT, PATH_PAYMENT ----------------------------------------------------------
    def _payment(self, source: str, dest: str, amount: int, asset=None) -> str:
        if amount <= 0:
            return "PAYMENT_MALFORMED"
        if dest == source:
            return "PAYMENT_SUCCESS"
        try:
            self._path_payment(source, dest, asset, amount, asset, amount, ())
        except _Fail as failure:
            return failure.args[0].replace("PATH_PAYMENT_", "PAYMENT_")
        return "PAYMENT_SUCCESS"

    def _path_payment(self, source, dest, send_asset, send_max, dest_asset, dest_amount, path) -> None:
        if dest_amount <= 0 or send_max <= 0:
            raise _Fail("PATH_PAYMENT_MALFORMED")
        full_path = (send_asset,) + tuple(path)
        to_issuer = (
            dest_asset is not None and len(full_path) == 1
            and send_asset == dest_asset and dest_asset[1] == dest
        )
        if not to_issuer and dest not in self.accounts:
            raise _Fail("PATH_PAYMENT_NO_DESTINATION")
        if dest_asset is None:
            self._move(dest, dest_amount)
        else:
            if not to_issuer and dest_asset[1] not in self.accounts:
                raise _Fail("PATH_PAYMENT_NO_ISSUER")
            line = self._line(dest, dest_asset)
            if line is None:
                raise _Fail("PATH_PAYMENT_NO_TRUST")
            if not line[2]:
                raise _Fail("PATH_PAYMENT_NOT_AUTHORIZED")
            if not self._line_add(dest, dest_asset, dest_amount):
                raise _Fail("PATH_PAYMENT_LINE_FULL")

        wanted, have = dest_amount, dest_asset
        for asset in reversed(full_path):
            if asset == have:
                continue
            if asset is not None and asset[1] not in self.accounts:
                raise _Fail("PATH_PAYMENT_NO_ISSUER")

            def no_own_offer(offer_id, offer):
                if offer[0] == source:
                    raise _Fail("PATH_PAYMENT_OFFER_CROSS_SELF")
                return True

            complete, sent, received = self._convert(asset, INT64_MAX, have, wanted, no_own_offer)
            if not complete or received != wanted:
                raise _Fail("PATH_PAYMENT_TOO_FEW_OFFERS")
            wanted, have = sent, asset

        if wanted > send_max:
            raise _Fail("PATH_PAYMENT_OVER_SENDMAX")
        if have is None:
            if self._balance(source) - wanted < self.min_balance(source):
                raise _Fail("PATH_PAYMENT_UNDERFUNDED")
            self._move(source, -wanted)
            return
        if not to_issuer and have[1] not in self.accounts:
            raise _Fail("PATH_PAYMENT_NO_ISSUER")
        line = self._line(source, have)
        if line is None:
            raise _Fail("PATH_PAYMENT_SRC_NO_TRUST")
        if not line[2]:
            raise _Fail("PATH_PAYMENT_SRC_NOT_AUTHORIZED")
        if not self._line_add(source, have, -wanted):
            raise _Fail("PATH_PAYMENT_UNDERFUNDED")

    # -- the exchange -------------------------------------------------------------------
    def book(self, selling, buying) -> List[int]:
        """Ids of the offers selling ``selling`` for ``buying``, cheapest
        first: by price, then by offer id."""
        ids = [i for i, o in self.offers.items() if o[1] == selling and o[2] == buying]
        ids.sort(key=lambda i: (Fraction(self.offers[i][4], self.offers[i][5]), i))
        return ids

    def _convert(self, sheep, max_sheep: int, wheat, max_wheat: int, keep) -> Tuple[bool, int, int]:
        """Send up to ``max_sheep`` for up to ``max_wheat`` through the
        book -> (whether the walk ended without a partial fill, sheep sent,
        wheat received).  ``keep(id, offer)``: True cross it, None skip it,
        False stop the walk as complete."""
        sent = received = 0
        if max_wheat <= 0 or max_sheep <= 0:
            return True, 0, 0
        for offer_id in self.book(wheat, sheep):
            verdict = keep(offer_id, self.offers[offer_id])
            if verdict is None:
                continue
            if verdict is False:
                return True, sent, received
            how, got_wheat, gave_sheep = self._cross(offer_id, max_wheat - received, max_sheep - sent)
            if how == "cannot":
                return False, sent, received
            sent += gave_sheep
            received += got_wheat
            if received >= max_wheat or sent >= max_sheep:
                return True, sent, received
            if how == "partial":
                return False, sent, received
        return True, sent, received

    def _cross(self, offer_id: int, max_wheat: int, max_sheep: int) -> Tuple[str, int, int]:
        seller, wheat, sheep, amount, n, d = self.offers[offer_id]
        if sheep is None:
            num_wheat = INT64_MAX
        else:
            num_wheat = _mul_div(self._room(self._line(seller, sheep)), d, n)
        if wheat is None:
            can_sell = max(self._balance(seller) - self.min_balance(seller), 0)
        else:
            line = self._line(seller, wheat)
            can_sell = line[0] if line is not None and line[2] else 0
        num_wheat = min(num_wheat, can_sell)
        if num_wheat >= amount:
            num_wheat = amount
        else:
            amount = num_wheat  # the offer shrinks to what its seller can do
        reduced = False
        if num_wheat > max_wheat:
            num_wheat, reduced = max_wheat, True
        num_sheep = _mul_div(num_wheat, n, d)
        if num_sheep > max_sheep:
            num_sheep, reduced = max_sheep, True
        num_wheat = _mul_div(num_sheep, d, n)  # towards the seller
        taken = False
        if num_wheat == 0 or num_sheep == 0:
            if reduced:
                return "cannot", 0, 0
            num_wheat = num_sheep = 0  # a worthless offer: deleted
            taken = True
        taken = taken or amount <= num_wheat
        if taken:
            self._put(self.offers, offer_id, None)
            self._sub_entry(seller, -1)
        else:
            self._put(self.offers, offer_id, (seller, wheat, sheep, amount - num_wheat, n, d))
        self.claimed += 1
        if num_sheep:
            if sheep is None:
                self._move(seller, num_sheep)
            elif not self._line_add(seller, sheep, num_sheep):
                raise AssertionError("a seller paid over its line's limit")
        if num_wheat:
            if wheat is None:
                self._move(seller, -num_wheat)
            elif not self._line_add(seller, wheat, -num_wheat):
                raise AssertionError("a seller delivered more than its line held")
        return ("taken" if taken else "partial"), num_wheat, num_sheep

    # -- MANAGE_OFFER -------------------------------------------------------------------
    def _manage_offer(self, source, sheep, wheat, amount: int, price, offer_id: int) -> None:
        n, d = price
        if sheep == wheat or amount < 0 or n <= 0 or d <= 0:
            raise _Fail("MANAGE_OFFER_MALFORMED")
        sheep_line = wheat_line = None
        if amount:
            if sheep is not None:
                sheep_line = self._line(source, sheep)
                if sheep[1] not in self.accounts:
                    raise _Fail("MANAGE_OFFER_SELL_NO_ISSUER")
                if sheep_line is None:
                    raise _Fail("MANAGE_OFFER_SELL_NO_TRUST")
                if sheep_line[0] == 0:
                    raise _Fail("MANAGE_OFFER_UNDERFUNDED")
                if not sheep_line[2]:
                    raise _Fail("MANAGE_OFFER_SELL_NOT_AUTHORIZED")
            if wheat is not None:
                wheat_line = self._line(source, wheat)
                if wheat[1] not in self.accounts:
                    raise _Fail("MANAGE_OFFER_BUY_NO_ISSUER")
                if wheat_line is None:
                    raise _Fail("MANAGE_OFFER_BUY_NO_TRUST")
                if not wheat_line[2]:
                    raise _Fail("MANAGE_OFFER_BUY_NOT_AUTHORIZED")
        creating = offer_id == 0
        if not creating:
            old = self.offers.get(offer_id)
            if old is None or old[0] != source:
                raise _Fail("MANAGE_OFFER_NOT_FOUND")
        mark = len(self._journal)
        try:
            self._cross_and_rest(source, sheep, wheat, amount, n, d, offer_id, sheep_line, wheat_line)
        except _Fail:
            self._undo(mark)  # the crossings of an offer that failed are undone with it
            raise

    def _cross_and_rest(self, source, sheep, wheat, amount, n, d, offer_id, sheep_line, wheat_line) -> None:
        creating = offer_id == 0
        left = 0
        if amount:
            if sheep is None:
                can_sell = max(self._balance(source) - self.min_balance(source), 0)
            else:
                can_sell = sheep_line[0]
            if wheat is None:
                can_receive = INT64_MAX
            else:
                can_receive = self._room(wheat_line)
                if can_receive == 0:
                    raise _Fail("MANAGE_OFFER_LINE_FULL")
            max_sheep = min(amount, can_sell, _mul_div(can_receive, d, n))

            def within_price(resting_id, resting):
                if resting_id == offer_id:
                    return None  # an offer never crosses itself
                if resting[4] * n > d * resting[5]:  # resting n/d above d/n
                    return False
                if resting[0] == source:
                    raise _Fail("MANAGE_OFFER_CROSS_SELF")
                return True

            _complete, sent, received = self._convert(sheep, max_sheep, wheat, can_receive, within_price)
            if received > 0:
                if wheat is None:
                    self._move(source, received)
                elif not self._line_add(source, wheat, received):
                    raise AssertionError("offer claimed over limit")
                if sheep is None:
                    self._move(source, -sent)
                elif not self._line_add(source, sheep, -sent):
                    raise AssertionError("offer sold more than balance")
            left = max_sheep - sent
        if left > 0:
            if creating:
                if not self._sub_entry(source, 1):
                    raise _Fail("MANAGE_OFFER_LOW_RESERVE")
                self._journal.append((None, None, self.id_pool))
                self.id_pool += 1
                offer_id = self.id_pool
            self._put(self.offers, offer_id, (source, sheep, wheat, left, n, d))
        elif not creating:
            self._put(self.offers, offer_id, None)
            self._sub_entry(source, -1)

    # -- CHANGE_TRUST -------------------------------------------------------------------
    def _change_trust(self, source, asset, limit: int) -> None:
        if limit < 0 or asset is None:
            raise _Fail("CHANGE_TRUST_MALFORMED")
        line = self._line(source, asset)
        if line is not None:
            if limit < line[0]:
                raise _Fail("CHANGE_TRUST_INVALID_LIMIT")
            if source == asset[1]:
                return  # the issuer's own line: nothing is stored
            if limit == 0:
                self._put(self.trustlines, (source, asset), None)
                self._sub_entry(source, -1)
                return
            if asset[1] not in self.accounts:
                raise _Fail("CHANGE_TRUST_NO_ISSUER")
            self._put(self.trustlines, (source, asset), [line[0], limit, line[2]])
            return
        if limit == 0:
            raise _Fail("CHANGE_TRUST_INVALID_LIMIT")
        if asset[1] not in self.accounts:
            raise _Fail("CHANGE_TRUST_NO_ISSUER")
        if not self._sub_entry(source, 1):
            raise _Fail("CHANGE_TRUST_LOW_RESERVE")
        authorised = not self.flags.get(asset[1], 0) & AUTH_REQUIRED
        self._put(self.trustlines, (source, asset), [0, limit, authorised])

    # -- SET_OPTIONS --------------------------------------------------------------------
    def _set_options(self, source, fields: dict) -> None:
        set_flags, clear_flags = fields.get("setFlags"), fields.get("clearFlags")
        if (set_flags or 0) & ~ALL_FLAGS or (clear_flags or 0) & ~ALL_FLAGS:
            raise _Fail("SET_OPTIONS_UNKNOWN_FLAG")
        if set_flags is not None and clear_flags is not None and set_flags & clear_flags:
            raise _Fail("SET_OPTIONS_BAD_FLAGS")
        names = ("masterWeight", "lowThreshold", "medThreshold", "highThreshold")
        if any(fields.get(f, 0) > 255 for f in names):
            raise _Fail("SET_OPTIONS_THRESHOLD_OUT_OF_RANGE")
        signer = fields.get("signer")
        if signer is not None and signer[0] == source:
            raise _Fail("SET_OPTIONS_BAD_SIGNER")
        for change, setting in ((clear_flags, False), (set_flags, True)):
            if change is None:
                continue
            flags = self.flags.get(source, 0)
            if change & ALL_FLAGS and flags & AUTH_IMMUTABLE:
                raise _Fail("SET_OPTIONS_CANT_CHANGE")
            self._put(self.flags, source, flags | change if setting else flags & ~change)
        thresholds = list(self.thresholds.get(source, (1, 0, 0, 0)))
        for i, f in enumerate(names):
            if f in fields:
                thresholds[i] = fields[f] & 0xFF
        self._put(self.thresholds, source, tuple(thresholds))
        if signer is None:
            return
        key, weight = signer
        mine = dict(self.signers.get(source, {}))
        if weight:
            if key not in mine:
                if len(mine) >= MAX_SIGNERS:
                    raise _Fail("SET_OPTIONS_TOO_MANY_SIGNERS")
                if not self._sub_entry(source, 1):
                    raise _Fail("SET_OPTIONS_LOW_RESERVE")
            mine[key] = weight
        elif key in mine:
            del mine[key]
            self._sub_entry(source, -1)
        self._put(self.signers, source, mine)


# -- a history archive, replayed plainly ----------------------------------------
#
# What a node's catch-up (CATCHUP_COMPLETE) must arrive at, from the files a
# publisher left in a history archive and nothing else.  Nothing here comes
# from ``stellar_tpu``: the record-marked XDR streams are walked with
# ``struct``, hashes are ``hashlib``'s, verdicts libsodium's through
# ``ctypes``, and the apply is the plain ledger above.  Operations other than
# CREATE_ACCOUNT and the native PAYMENT are refused: the deployments that
# publish these archives make no others.
#
# ``tests/reference_apply.py`` and ``benchmarks/reference_replay.py`` hold this
# section letter for letter (tier-1 compares them).

import base64
import ctypes
import ctypes.util
import gzip
import hashlib
import os
import struct

ENVELOPE_TYPE_TX = 2
TX_RESULT_CODES = {
    0: "txSUCCESS", -1: "txFAILED", -2: "txTOO_EARLY", -3: "txTOO_LATE", -4: "txMISSING_OPERATION",
    -5: "txBAD_SEQ", -6: "txBAD_AUTH", -7: "txINSUFFICIENT_BALANCE", -8: "txNO_ACCOUNT",
    -9: "txINSUFFICIENT_FEE", -10: "txBAD_AUTH_EXTRA", -11: "txINTERNAL_ERROR",
}


class _Cursor:
    """A reading position in XDR bytes."""

    def __init__(self, data: bytes, at: int = 0):
        self.data, self.at = data, at

    def take(self, n: int) -> bytes:
        if self.at + n > len(self.data):
            raise ValueError("XDR runs past the end of its record")
        out = self.data[self.at : self.at + n]
        self.at += n
        return out

    def u32(self) -> int:
        return struct.unpack(">I", self.take(4))[0]

    def i32(self) -> int:
        return struct.unpack(">i", self.take(4))[0]

    def u64(self) -> int:
        return struct.unpack(">Q", self.take(8))[0]

    def i64(self) -> int:
        return struct.unpack(">q", self.take(8))[0]

    def opaque(self) -> bytes:
        n = self.u32()
        out = self.take(n)
        self.take(-n % 4)
        return out

    def key(self) -> bytes:
        if self.i32() != 0:
            raise ValueError("a public key that is not ed25519")
        return self.take(32)


def archive_file(archive_dir: str, category: str, checkpoint: int) -> str:
    """Where an archive keeps a checkpoint's file of ``category``
    (``ledger``, ``transactions``, ``results``)."""
    h = "%08x" % checkpoint
    return os.path.join(archive_dir, category, h[0:2], h[2:4], h[4:6], f"{category}-{h}.xdr.gz")


def records(path: str) -> List[bytes]:
    """The bodies of a gzipped record-marked XDR file (RFC 5531: four bytes
    of length with the high bit set, then the body)."""
    with gzip.open(path, "rb") as f:
        data = f.read()
    out, at = [], 0
    while at < len(data):
        (mark,) = struct.unpack_from(">I", data, at)
        n = mark & 0x7FFFFFFF
        if at + 4 + n > len(data):
            raise ValueError(f"{path}: truncated record")
        out.append(data[at + 4 : at + 4 + n])
        at += 4 + n
    return out


def header_entry(body: bytes) -> dict:
    """A LedgerHeaderHistoryEntry: the hash the archive claims, and the
    header's fields with the bytes they were read from."""
    c = _Cursor(body)
    claimed = c.take(32)
    start = c.at
    h = {"claimed_hash": claimed, "version": c.u32(), "previous": c.take(32)}
    h["tx_set_hash"], h["close_time"] = c.take(32), c.u64()
    h["upgrades"] = [c.opaque() for _ in range(c.u32())]
    c.i32()  # the value's ext
    h["tx_result_hash"], h["bucket_list_hash"] = c.take(32), c.take(32)
    h["seq"], h["total_coins"], h["fee_pool"] = c.u32(), c.i64(), c.i64()
    h["inflation_seq"], h["id_pool"] = c.u32(), c.u64()
    h["base_fee"], h["base_reserve"], h["max_tx_set_size"] = c.u32(), c.u32(), c.u32()
    c.take(4 * 32)  # the skip list
    c.i32()  # the header's ext
    h["hash"] = hashlib.sha256(body[start : c.at]).digest()
    return h


def _operation(c: _Cursor) -> tuple:
    if c.u32():
        raise ValueError("an operation with a source of its own")
    kind = c.i32()
    if kind == 0:
        return ("create", c.key(), c.i64())
    if kind == 1:
        dest = c.key()
        if c.i32() != 0:
            raise ValueError("a payment that is not native")
        return ("pay", dest, c.i64())
    raise ValueError(f"operation type {kind}: not one this replay applies")


def envelope(c: _Cursor, network_id: bytes) -> dict:
    """A TransactionEnvelope at the cursor: the transaction as the plain
    ledger takes it, its hash as the network signs it, the envelope's bytes
    and its signatures."""
    start = c.at
    source, fee, seq = c.key(), c.u32(), c.u64()
    if c.u32():
        c.take(16)  # time bounds: the replay applies none
    memo = c.i32()
    if memo == 1:
        c.opaque()
    elif memo == 2:
        c.take(8)
    elif memo in (3, 4):
        c.take(32)
    ops = tuple(_operation(c) for _ in range(c.u32()))
    c.i32()  # the transaction's ext
    tx_bytes = c.data[start : c.at]
    signatures = [(c.take(4), c.opaque()) for _ in range(c.u32())]
    contents = hashlib.sha256(network_id + struct.pack(">i", ENVELOPE_TYPE_TX) + tx_bytes).digest()
    return {
        "source": source, "fee": fee, "seq": seq, "ops": ops, "hash": contents,
        "signatures": signatures, "bytes": c.data[start : c.at],
    }


def tx_entry(body: bytes, network_id: bytes) -> Tuple[int, bytes, List[dict]]:
    """A TransactionHistoryEntry -> (ledger, the set's previous ledger hash,
    its envelopes in the file's order)."""
    c = _Cursor(body)
    seq, previous = c.u32(), c.take(32)
    return seq, previous, [envelope(c, network_id) for _ in range(c.u32())]


def result_entry(body: bytes) -> Tuple[int, List[Tuple[bytes, int, str]]]:
    """A TransactionHistoryResultEntry -> (ledger, [(transaction hash, fee
    charged, code)] in the order the publisher applied them).  An
    operation's result is walked over: every one this replay applies is an
    ``opINNER`` of a type and a code with nothing after."""
    c = _Cursor(body)
    seq, out = c.u32(), []
    for _ in range(c.u32()):
        tx_hash, fee, code = c.take(32), c.i64(), c.i32()
        if code in (0, -1):
            for _ in range(c.u32()):
                if c.i32() == 0:
                    c.take(8)
        c.i32()  # the result's ext
        out.append((tx_hash, fee, TX_RESULT_CODES[code]))
    return seq, out


def _sodium() -> ctypes.CDLL:
    name = ctypes.util.find_library("sodium")
    for cand in ([name] if name else []) + ["libsodium.so.23", "libsodium.so"]:
        try:
            lib = ctypes.CDLL(cand)
        except OSError:
            continue
        if lib.sodium_init() < 0:
            raise RuntimeError("sodium_init failed")
        return lib
    raise RuntimeError("libsodium not found: no reference for signatures")


def root_key(network_id: bytes) -> bytes:
    """The genesis account's public key: the network id is its seed."""
    pk, sk = ctypes.create_string_buffer(32), ctypes.create_string_buffer(64)
    _sodium().crypto_sign_seed_keypair(pk, sk, network_id)
    return pk.raw


def replay_archive(archive_dir: str, checkpoint: int, passphrase: str) -> dict:
    """Replay the checkpoint ``[1, checkpoint]`` of a fresh network from its
    archive files.  -> what a caught-up node has to show:

    ``hashes`` {ledger: header hash}, ``bucket_list_hash`` and ``fee_pool`` of
    the anchor, ``accounts`` {raw key: (balance, sequence number)}, ``txs``
    and ``signatures`` replayed — and the faults found on the way, each a
    count that has to be 0 in an honest archive: ``headers_off`` (a header
    whose bytes do not hash to the claimed hash, or that does not name the
    header before it), ``sets_off`` (a set that is not on its ledger's
    previous hash or does not hash to the header's ``txSetHash``),
    ``signatures_bad`` (libsodium's verdict), ``results_off`` (a
    transaction whose code or fee in the results file is not the plain
    ledger's, a results order that is no order of the set) and
    ``fee_pools_off`` (a header whose fee pool is not the plain ledger's)."""
    network_id = hashlib.sha256(passphrase.encode()).digest()
    headers = {h["seq"]: h for h in map(header_entry, records(archive_file(archive_dir, "ledger", checkpoint)))}
    sets = {
        seq: (previous, envs)
        for seq, previous, envs in (
            tx_entry(b, network_id) for b in records(archive_file(archive_dir, "transactions", checkpoint))
        )
    }
    results = dict(map(result_entry, records(archive_file(archive_dir, "results", checkpoint))))
    out = {"headers_off": 0, "sets_off": 0, "signatures_bad": 0, "results_off": 0, "fee_pools_off": 0}
    for seq in sorted(headers):
        h = headers[seq]
        before = headers.get(seq - 1)
        if h["hash"] != h["claimed_hash"] or (before is not None and h["previous"] != before["hash"]):
            out["headers_off"] += 1
    if sorted(headers) != list(range(1, checkpoint + 1)):
        out["headers_off"] += 1

    lib = _sodium()
    verify = lib.crypto_sign_verify_detached
    verify.argtypes = [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_ulonglong, ctypes.c_char_p]
    genesis = headers[1]
    ledger = Ledger(
        {root_key(network_id): (genesis["total_coins"], 0)}, genesis["base_fee"], genesis["base_reserve"],
    )
    txs = signatures = 0
    for seq in range(2, checkpoint + 1):
        h = headers[seq]
        previous, envs = sets.get(seq, (h["previous"], []))
        contents = hashlib.sha256(
            previous + b"".join(e["bytes"] for e in sorted(envs, key=lambda e: hashlib.sha256(e["bytes"]).digest()))
        ).digest()
        if previous != h["previous"] or contents != h["tx_set_hash"]:
            out["sets_off"] += 1
        by_hash = {}
        for e in envs:
            signed_by = []
            for hint, sig in e["signatures"]:
                signatures += 1
                ok = len(sig) == 64 and hint == e["source"][-4:] and verify(sig, e["hash"], 32, e["source"]) == 0
                out["signatures_bad"] += 0 if ok else 1
                signed_by.append(e["source"] if ok else b"")
            by_hash[e["hash"]] = Tx(e["source"], e["seq"], e["fee"], e["ops"], tuple(signed_by))
        stored = results.get(seq, [])
        order = [by_hash.get(tx_hash) for tx_hash, _fee, _code in stored]
        if len(stored) != len(by_hash) or None in order or len({t for t, _f, _c in stored}) != len(stored):
            out["results_off"] += len(by_hash)
            order = list(by_hash.values())
            stored = []
        pool = ledger.fee_pool
        codes = ledger.close(seq, order)
        for (code, _ops), (_tx_hash, fee, have), tx in zip(codes, stored, order):
            if code != have or fee != tx.fee:
                out["results_off"] += 1
        txs += len(order)
        # a version or fee upgrade would change the arithmetic: none is applied here
        for up in h["upgrades"]:
            if struct.unpack(">i", up[:4])[0] != 3:
                raise ValueError("an upgrade that is not of maxTxSetSize")
        if ledger.fee_pool != h["fee_pool"] or ledger.fee_pool - pool != sum(t.fee for t in order):
            out["fee_pools_off"] += 1
    anchor = headers[checkpoint]
    out.update(
        hashes={seq: h["hash"] for seq, h in headers.items()},
        bucket_list_hash=anchor["bucket_list_hash"], fee_pool=ledger.fee_pool,
        accounts={k: tuple(v) for k, v in ledger.accounts.items()}, txs=txs, signatures=signatures,
    )
    return out


def stored_accounts(db_path: str) -> Dict[bytes, Tuple[int, int]]:
    """{raw key: (balance, sequence number)} as a database file holds them,
    read by ``sqlite3`` alone (an account id is a strkey: a version byte,
    the key and a checksum in base32)."""
    import sqlite3

    con = sqlite3.connect(f"file:{db_path}?mode=ro", uri=True)
    try:
        rows = con.execute("SELECT accountid, balance, seqnum FROM accounts").fetchall()
    finally:
        con.close()
    return {base64.b32decode(aid)[1:33]: (balance, seq) for aid, balance, seq in rows}
