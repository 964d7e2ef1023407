"""``mixed_closes``: the ``closes`` traffic with LoadGenerator's operation mix
— consecutive full transaction sets of one-operation transactions, 15 %
CHANGE_TRUST, 15 % credit payments (half of them PATH_PAYMENT through the
book), 10 % MANAGE_OFFER and 60 % that touch native balances only (PAYMENT,
CREATE_ACCOUNT, SET_OPTIONS), each set validated and closed through the
node's own close path (``closes``'s ``step``).

Set-up, through closed ledgers after ``fund``: the issuers, every account's
trustlines and credit, and the resting book (one offer on each side of one
native/credit book for half the accounts).  Then every set of the window is
planned (``Planner``), built and signed before the window opens.

**The planner tracks state with the plain ledger** (``reference_mixed
.Ledger``) so that each transaction meets its preconditions, or, for the
configuration's failing share, misses the one intended.  A set's apply
order is fixed by a hash that includes the previous ledger's, which does
not exist yet when the set is planned, so a set is planned to come out the
same in any order:

- a set's sources are distinct accounts, and its destinations are accounts
  that are no source of the set;
- every amount that meets the book is a multiple of the price ladder's
  denominator, so that the exchange's two floors (the plain ledger's
  docstring) divide exactly and nothing is delivered short;
- each set a seeded half of the books is *taken from* and the other half
  *made on*.  Takers — path payments, and arriving offers priced to cross —
  together ask a book side for no more than its best price level holds, so
  each is served whole at that one price whatever the order, and an
  arriving taker's amount is a multiple of that price's numerator.  Makers
  — arriving offers priced to rest, and updates and deletes by id — go to
  books nobody takes from in that set, never at a price that would cross
  the other side nor at one better than their own side's best (a level of
  one offer could not serve the next set's takers), and first to the sides
  whose best level is thin, so that what is placed is about what is taken;
- a maker's offer is updated or deleted by id only where the id is certain:
  the ids of offers that came to rest inside the window depend on the
  apply order, those of the set-up's do not;
- a taker is never a maker of the book it takes from (no accidental
  ``CROSS_SELF``), except the ones built to fail.

What the planner believes is therefore exact in what it plans by — every
price level's depth, every set-up offer, every balance within the margins
it keeps — and not in the ids, or the order inside a level, of offers placed
in one set of the window.  The check does not use it: ``check`` replays every
closed set on a fresh plain ledger in the order ``txhistory`` gives.

``commit.flush``'s row counts, ``apply.serial``'s ``failed``, ``tx.apply``'s
``op`` and the ``op.exchange`` spans are the program's, and
``benchmarks/spans.compact`` keeps the attributes of two other span names
only; ``drain_spans`` repeats what the layer readers need on spans of the
harness's own (``bench.flush_rows``, ``bench.apply_failed``,
``bench.tx_apply_op``, ``bench.exchange``), each of no length at the end of
the span it repeats (so that it is never the innermost span of an idle
gap).  ``counters`` adds the node's ``exchange`` block.  On a program
without them (the parent commit) nothing is repeated and the readers
return nothing.
"""

from __future__ import annotations

import hashlib
import math
import random
import statistics
import time
from typing import Dict, List, Optional, Tuple

from benchmarks import node as N
from benchmarks import reference_mixed as RM
from benchmarks.generators import closes
from benchmarks.stats import Reading

LIMIT = 10**12  # a trustline's limit
CODE = "IS%02d"


# -- plain transactions as envelopes --------------------------------------------------


def public_key(name: str):
    from stellar_tpu.crypto.keys import PubKeyUtils

    return PubKeyUtils.from_strkey(name)


def xdr_asset(asset):
    import stellar_tpu.xdr as X

    if asset is None:
        return X.Asset.native()
    return X.Asset.alphanum4(asset[0].encode(), public_key(asset[1]))


def xdr_op(op: tuple):
    """The XDR operation of a plain ledger's operation tuple."""
    import stellar_tpu.xdr as X

    T = X.OperationType
    kind = op[0]
    if kind == "pay":
        asset = op[3] if len(op) > 3 else None
        body = X.OperationBody(T.PAYMENT, X.PaymentOp(public_key(op[1]), xdr_asset(asset), op[2]))
    elif kind == "create":
        body = X.OperationBody(T.CREATE_ACCOUNT, X.CreateAccountOp(public_key(op[1]), op[2]))
    elif kind == "path":
        _, dest, send_asset, send_max, dest_asset, dest_amount, path = op
        body = X.OperationBody(
            T.PATH_PAYMENT,
            X.PathPaymentOp(
                xdr_asset(send_asset), send_max, public_key(dest), xdr_asset(dest_asset), dest_amount,
                [xdr_asset(a) for a in path],
            ),
        )
    elif kind == "offer":
        _, selling, buying, amount, (n, d), offer_id = op
        body = X.OperationBody(
            T.MANAGE_OFFER, X.ManageOfferOp(xdr_asset(selling), xdr_asset(buying), amount, X.Price(n, d), offer_id)
        )
    elif kind == "trust":
        body = X.OperationBody(T.CHANGE_TRUST, X.ChangeTrustOp(xdr_asset(op[1]), op[2]))
    elif kind == "options":
        f = dict(op[1])
        signer = f.get("signer")
        body = X.OperationBody(
            T.SET_OPTIONS,
            X.SetOptionsOp(
                None, f.get("clearFlags"), f.get("setFlags"), f.get("masterWeight"), f.get("lowThreshold"),
                f.get("medThreshold"), f.get("highThreshold"), None,
                None if signer is None else X.Signer(public_key(signer[0]), signer[1]),
            ),
        )
    else:
        raise ValueError(kind)
    return X.Operation(None, body)


def frame_of(network_id: bytes, tx: RM.Tx, secrets: dict):
    """The signed TransactionFrame of a plain transaction.  ``secrets``:
    strkey -> SecretKey, for the source and for whoever signs."""
    import stellar_tpu.xdr as X
    from stellar_tpu.tx.frame import TransactionFrame

    body = X.Transaction(
        sourceAccount=secrets[tx.source].get_public_key(), fee=tx.fee, seqNum=tx.seq, timeBounds=None,
        memo=X.Memo.none(), operations=[xdr_op(op) for op in tx.ops], ext=0,
    )
    frame = TransactionFrame(network_id, X.TransactionEnvelope(body, []))
    for name in tx.signed_by if tx.signed_by is not None else (tx.source,):
        frame.add_signature(secrets[name])
    return frame


# -- the planner ------------------------------------------------------------------------

KINDS = ("trust", "credit", "path", "offer", "native", "create", "options")
FAILURES = ("underfunded", "line_full", "no_trust", "too_few_offers", "over_sendmax", "cross_self")


class Planner:
    """Plans sets of the mix over a plain ledger it keeps (module docstring).

    ``shape`` (the configuration's ``shape`` block): the shares of a set by
    kind, the price ladder, amounts and the failing share."""

    # which kinds of transaction can carry which failure
    CARRIERS = {
        "underfunded": ("native",), "line_full": ("credit",), "no_trust": ("credit",),
        "too_few_offers": ("path",), "over_sendmax": ("path",), "cross_self": ("offer", "path"),
    }

    def __init__(self, ledger: RM.Ledger, accounts: List[str], issuers: List[str], signer_keys: Dict[str, str],
                 new_account, fee: int, shape: dict, seed: int):
        self.ledger = ledger
        self.accounts = accounts
        self.assets = [(CODE % i, name) for i, name in enumerate(issuers)]
        self.signer_keys = signer_keys  # account -> the key it adds as a signer
        self.new_account = new_account  # i -> the name of the i-th account a set creates
        self.fee = fee
        self.shape = shape
        self.denominator = int(shape["price_denominator"])
        self.ladder = [int(k) for k in shape["price_ladder"]]
        self.rng = random.Random((seed << 8) ^ 0x4D58)
        self.created = 0
        self.turn = 0  # which failure is built next
        self.uncertain: set = set()  # ids of offers that came to rest in the window
        self.depth_at_start: Dict[tuple, int] = {}  # side -> what the set-up gave it
        self.makers: Dict[tuple, set] = {}  # asset -> accounts that ever rested an offer on its book
        self.tally = {k: 0 for k in KINDS}
        self.tally.update({"built_to_fail": 0, "conversions": 0, "two_conversions": 0, "offers_crossing": 0,
                           "offers_resting": 0, "offers_updated": 0, "offers_deleted": 0, "fallbacks": 0,
                           "signed_by_signer": 0})

    # -- one set -----------------------------------------------------------------------
    def plan(self, width: int, ledger_seq: int) -> List[Tuple[RM.Tx, str]]:
        """One set -> [(transaction, what it is)].  The planner's ledger is
        moved on by the set, applied in the order planned."""
        rng, ledger, shape = self.rng, self.ledger, self.shape
        order = list(range(len(self.accounts)))
        rng.shuffle(order)
        sources = [self.accounts[i] for i in order[:width]]
        self.idle = [self.accounts[i] for i in order[width:]]
        counts = {k: int(round(width * shape["shares"][k])) for k in KINDS}
        counts["native"] += width - sum(counts.values())
        kinds = [k for k in KINDS for _ in range(counts[k])]
        rng.shuffle(kinds)

        # the book as the set finds it: side -> price numerator -> ids by id
        self.sides: Dict[tuple, Dict[int, List[int]]] = {}
        self.selling: Dict[str, Dict[tuple, int]] = {}  # account -> credit its resting offers sell
        for oid in sorted(ledger.offers):
            seller, selling, buying, amount, n, _d = ledger.offers[oid]
            self.sides.setdefault((selling, buying), {}).setdefault(n, []).append(oid)
            self.makers.setdefault(selling or buying, set()).add(seller)
            if selling is not None:
                mine = self.selling.setdefault(seller, {})
                mine[selling] = mine.get(selling, 0) + amount
        books = list(self.assets)
        rng.shuffle(books)
        self.taken_from = set(books[: len(books) // 2])
        self.budget: Dict[tuple, int] = {}
        for asset in self.taken_from:
            for side in ((asset, None), (None, asset)):
                levels = self.sides.get(side)
                if levels:
                    self.budget[side] = sum(ledger.offers[i][3] for i in levels[min(levels)])
        if not self.depth_at_start:
            self.depth_at_start = {side: self._depth(side) for side in self.sides}
        self.rested: Dict[tuple, int] = {}  # side -> best numerator placed in this set
        self.placed: Dict[tuple, int] = {}  # side -> amount placed at its best level in this set

        # the failures of this set, in turn, each on a transaction of a kind that can carry it
        fail_at: Dict[int, str] = {}
        free = {k: [i for i, kind in enumerate(kinds) if kind == k] for k in KINDS}
        for _ in range(int(round(width * shape["failing_share"]))):
            which = FAILURES[self.turn % len(FAILURES)]
            self.turn += 1
            slots = [(k, i) for k in self.CARRIERS[which] for i in free[k]
                     if which != "cross_self" or self._own_ask(sources[i]) is not None]
            if slots:
                k, i = slots[rng.randrange(len(slots))]
                free[k].remove(i)
                fail_at[i] = which

        out = []
        for i, (source, kind) in enumerate(zip(sources, kinds)):
            op = None
            if i in fail_at:
                op, what = getattr(self, "_fail_" + fail_at[i])(source, kind), "fail:" + fail_at[i]
            if op is not None:
                self.tally["built_to_fail"] += 1
                self.tally[kind] += 1
            else:
                op, what = getattr(self, "_" + kind)(source), kind
                if op is None:  # nothing of the kind can be built for this source
                    self.tally["fallbacks"] += 1
                    op, what = self._native(source), "native"
                self.tally[what] += 1
            signed_by = None
            mine = ledger.signers.get(source)
            if mine and rng.random() < 0.5:
                signed_by = (next(iter(mine)),)
                self.tally["signed_by_signer"] += 1
            out.append((RM.Tx(source, ledger.accounts[source][1] + 1, self.fee, (op,), signed_by), what))
        before = set(ledger.offers)
        ledger.close(ledger_seq, [tx for tx, _what in out])
        self.uncertain |= set(ledger.offers) - before
        return out

    # -- helpers ---------------------------------------------------------------------------
    def _pick(self, seq: list):
        return seq[self.rng.randrange(len(seq))] if seq else None

    def _shuffled(self, seq) -> list:
        seq = list(seq)
        self.rng.shuffle(seq)
        return seq

    def _amount(self, name: str) -> int:
        low, high = self.shape[name]
        return self.denominator * self.rng.randrange(low // self.denominator, high // self.denominator + 1)

    def _idle_with(self, test) -> Optional[str]:
        """An account that is no source of this set and passes ``test``."""
        return self._pick([d for d in self.rng.sample(self.idle, min(40, len(self.idle))) if test(d)])

    def _trusting(self, asset) -> Optional[str]:
        lines = self.ledger.trustlines
        return self._idle_with(lambda d: (d, asset) in lines and lines[(d, asset)][2])

    def _holding(self, source: str, at_least: int) -> list:
        """Assets the source holds ``at_least`` of beyond what its own
        resting offers sell."""
        lines, selling = self.ledger.trustlines, self.selling.get(source, {})
        return [
            a for a in self.assets
            if (source, a) in lines and lines[(source, a)][0] - selling.get(a, 0) >= at_least
        ]

    def _depth(self, side: tuple) -> int:
        return sum(self.ledger.offers[i][3] for ids in self.sides.get(side, {}).values() for i in ids)

    def _take(self, side: tuple, wheat: int) -> Optional[int]:
        """Reserve ``wheat`` of the side's best level -> its price's
        numerator, or None where the level does not hold that much more."""
        if self.budget.get(side, 0) < wheat:
            return None
        self.budget[side] -= wheat
        return min(self.sides[side])

    def _takes_from(self, source: str) -> list:
        """The books taken from in this set that the source never made on."""
        return self._shuffled(a for a in sorted(self.taken_from) if source not in self.makers.get(a, ()))

    # -- the kinds -----------------------------------------------------------------------
    def _native(self, source: str) -> tuple:
        return ("pay", self._pick(self.idle), int(self.shape["native_amount"]))

    def _create(self, source: str) -> tuple:
        self.created += 1
        return ("create", self.new_account(self.created), int(self.shape["create_balance"]))

    def _options(self, source: str) -> tuple:
        """Adds the account's second signer, or removes the one it has."""
        weight = 0 if self.ledger.signers.get(source) else 1
        return ("options", (("signer", (self.signer_keys[source], weight)),))

    def _trust(self, source: str) -> tuple:
        lines = self.ledger.trustlines
        missing = [a for a in self.assets if (source, a) not in lines]
        if missing:
            return ("trust", self._pick(missing), LIMIT)
        return ("trust", self._pick(self.assets), LIMIT + self.rng.randrange(1, 1000))

    def _credit(self, source: str) -> Optional[tuple]:
        amount = self._amount("credit_amount")
        for asset in self._shuffled(self._holding(source, amount)):
            dest = self._trusting(asset)
            if dest is not None:
                return ("pay", dest, amount, asset)
        return None

    def _path(self, source: str) -> Optional[tuple]:
        """Native sent, a credit delivered through that credit's asks; one in
        ``two_conversion_share`` sends a credit it holds instead, through
        that credit's bids into native and on."""
        den = self.denominator
        two = self.rng.random() < self.shape["two_conversion_share"]
        want = den * den if two else self._amount("path_amount")
        for asset in self._takes_from(source):
            dest = self._trusting(asset)
            if dest is None:
                continue
            k1 = self._take((asset, None), want)
            if k1 is None:
                continue
            if not two:
                self.tally["conversions"] += 1
                return ("path", dest, None, 2 * want * k1 // den, asset, want, ())
            native = want * k1 // den
            for held in self._shuffled(a for a in self._holding(source, 2 * want) if a in self.taken_from):
                if held == asset or source in self.makers.get(held, ()):
                    continue
                k2 = self._take((None, held), native)
                if k2 is not None:
                    self.tally["conversions"] += 2
                    self.tally["two_conversions"] += 1
                    return ("path", dest, held, 2 * native * k2 // den, asset, want, (None,))
            self.budget[(asset, None)] += want
            two, want = False, self._amount("path_amount")
        return None

    def _offer(self, source: str) -> Optional[tuple]:
        """By id where the source has an offer that can be named (which one
        account in four has, so those do it ``offer_by_id_share`` x 4 of the
        time); else a new offer, a seeded half priced to cross."""
        shape = self.shape
        op = None
        if self.rng.random() < 4 * shape["offer_by_id_share"]:
            op = self._offer_by_id(source)
        if op is None:
            first, second = self._offer_crossing, self._offer_resting
            if self.rng.random() < 0.5:
                first, second = second, first
            op = first(source) or second(source)
        return op

    def _offer_crossing(self, source: str) -> Optional[tuple]:
        """An arriving offer priced to cross, sized to be served whole by the
        best level of the side it takes from."""
        den, lines = self.denominator, self.ledger.trustlines
        lots = self._amount("offer_take") // den
        for asset in self._takes_from(source):
            if (source, asset) not in lines:
                continue
            sell_native = self.rng.random() < 0.5 or asset not in self._holding(source, 2 * lots * den)
            side = (asset, None) if sell_native else (None, asset)  # the side it takes from
            if not self.sides.get(side):
                continue
            best = min(self.sides[side])
            steps = [k for k in self.ladder if k * best <= den * den]
            if not steps or self._take(side, lots * den) is None:
                continue
            self.tally["offers_crossing"] += 1
            return ("offer", side[1], side[0], lots * best, (self._pick(steps), den), 0)
        return None

    def _can_rest(self, side: tuple, k: int) -> bool:
        """Whether an offer at k on ``side`` would leave the side's best
        price as it is (a better one would be a level of one offer, which
        the next set's takers could not share) and cross nothing on the
        other side, resting or placed in this set."""
        if self.sides.get(side) and k < min(self.sides[side]):
            return False
        other = (side[1], side[0])
        bests = [min(self.sides[other])] if self.sides.get(other) else []
        if other in self.rested:
            bests.append(self.rested[other])
        return k >= self.denominator and all(k * b > self.denominator**2 for b in bests)

    def _rest_at(self, side: tuple, k: int, amount: int) -> None:
        self.rested[side] = min(k, self.rested.get(side, k))
        if self.sides.get(side) and k == min(self.sides[side]):
            self.placed[side] = self.placed.get(side, 0) + amount

    def _best_depth(self, side: tuple) -> int:
        levels = self.sides.get(side)
        if not levels:
            return 0
        return sum(self.ledger.offers[i][3] for i in levels[min(levels)]) + self.placed.get(side, 0)

    def _offer_resting(self, source: str) -> Optional[tuple]:
        """An arriving offer priced to rest, on the thinnest side of a book
        nobody takes from in this set that the source can make on."""
        lines = self.ledger.trustlines
        amount = self._amount("offer_rest")
        holding = self._holding(source, 2 * amount)
        sides = [s for a in self.assets if a not in self.taken_from and (source, a) in lines
                 for s in ((a, None), (None, a)) if s[0] is None or a in holding]
        target = int(self.shape["best_level_target"])
        thin = lambda s: (self._best_depth(s) >= target, self._depth(s) // (8 * amount), self.rng.random())  # noqa: E731
        for side in sorted(sides, key=thin):
            steps = [k for k in self.ladder if self._can_rest(side, k)]
            if steps:
                # the best level first, until it holds what a set's takers may ask
                k = steps[0] if self._best_depth(side) < target else self._pick(steps)
                if self._depth(side) > self.depth_at_start.get(side, 0):
                    # the side holds more than the set-up gave it: a small
                    # offer, so that what is placed stays about what is taken
                    amount = self._amount("offer_take")
                self._rest_at(side, k, amount)
                self.makers.setdefault(side[0] or side[1], set()).add(source)
                self.tally["offers_resting"] += 1
                return ("offer", side[0], side[1], amount, (k, self.denominator), 0)
        return None

    def _offer_by_id(self, source: str) -> Optional[tuple]:
        """An update (a new amount, and half the time a new price that
        crosses nothing) or a delete of an offer of the source's whose id is
        certain, on a book nobody takes from in this set."""
        den = self.denominator
        for side, levels in self.sides.items():
            if (side[0] or side[1]) in self.taken_from:
                continue
            for n, ids in levels.items():
                for oid in ids:
                    if self.ledger.offers[oid][0] != source or oid in self.uncertain:
                        continue
                    if self.rng.random() < self.shape["delete_share_of_by_id"]:
                        self.tally["offers_deleted"] += 1
                        return ("offer", side[0], side[1], 0, (n, den), oid)
                    steps = [k for k in self.ladder if k != n and self._can_rest(side, k)]
                    k = self._pick(steps) if steps and self.rng.random() < 0.5 else n
                    amount = self._amount("offer_rest")
                    if side[0] is not None and side[0] not in self._holding(source, 2 * amount):
                        amount = self.ledger.offers[oid][3]
                    if k != n:
                        self._rest_at(side, k, amount)
                    self.tally["offers_updated"] += 1
                    return ("offer", side[0], side[1], amount, (k, den), oid)
        return None

    # -- built to fail at apply ---------------------------------------------------------------
    def _fail_underfunded(self, source, kind):
        return ("pay", self._pick(self.idle), self.ledger.accounts[source][0])

    def _fail_line_full(self, source, kind):
        lines = self.ledger.trustlines
        for asset in self._shuffled(self._holding(source, 1)):
            dest = self._trusting(asset)
            if dest is not None:
                balance, limit, _ = lines[(dest, asset)]
                return ("pay", dest, limit - balance + 1, asset)
        return None

    def _fail_no_trust(self, source, kind):
        lines = self.ledger.trustlines
        for asset in self._shuffled(self._holding(source, self.denominator)):
            dest = self._idle_with(lambda d: (d, asset) not in lines)
            if dest is not None:
                return ("pay", dest, self.denominator, asset)
        return None

    def _a_book_for(self, source):
        """(asset, a destination that trusts it) on a book with asks that
        the source never made on."""
        for asset in self._shuffled(self.assets):
            if source in self.makers.get(asset, ()) or not self.sides.get((asset, None)):
                continue
            dest = self._trusting(asset)
            if dest is not None:
                return asset, dest
        return None, None

    def _fail_too_few_offers(self, source, kind):
        """Far more than the whole side holds, or can come to hold in the set."""
        asset, dest = self._a_book_for(source)
        if asset is None:
            return None
        den = self.denominator
        want = den * (10 * self._depth((asset, None)) // den + 100000)
        return ("path", dest, None, 2 * want, asset, want, ())

    def _fail_over_sendmax(self, source, kind):
        asset, dest = self._a_book_for(source)
        if asset is None:
            return None
        return ("path", dest, None, 1, asset, self.denominator, ())

    def _own_ask(self, source: str):
        """The credit of a book nobody takes from in this set on which an
        ask of the source's rests for certain (a set-up offer), or None."""
        for (asset, buying), levels in self.sides.items():
            if asset is None or buying is not None or asset in self.taken_from:
                continue
            if any(self.ledger.offers[i][0] == source and i not in self.uncertain
                   for ids in levels.values() for i in ids):
                return asset
        return None

    def _fail_cross_self(self, source, kind):
        """A maker reaching for its own resting ask: with an arriving offer
        that would buy the whole side, or with a path payment that asks the
        side for all it holds."""
        asset = self._own_ask(source)
        if asset is None:
            return None
        reach = 10 * self._depth((asset, None)) + 10**7  # past whatever comes to rest before it in the set
        dest = self._trusting(asset)
        if kind == "offer" or dest is None:
            return ("offer", None, asset, reach, (min(self.ladder), self.denominator), 0)
        return ("path", dest, None, 2 * reach, asset, reach, ())


def set_up_phases(ledger: RM.Ledger, accounts: List[str], issuers: List[str], per_account: int, shape: dict,
                  fee: int, rng: random.Random):
    """The state a window starts from, as lists of transactions to close one
    list after the other (``ledger`` moved on by the caller in between: it
    gives the sequence numbers): every account trusts ``per_account``
    issuers, the first its book's; each is paid ``credit_holding`` of their
    credit, a hundred payments a transaction; the first half of the
    accounts rest an ask, then a bid, on their book, at the ladder's steps
    above 1."""
    assets = [(CODE % i, name) for i, name in enumerate(issuers)]
    den = int(shape["price_denominator"])
    seq: Dict[str, int] = {}

    def tx(source: str, ops: list) -> RM.Tx:
        seq[source] = max(seq.get(source, 0), ledger.accounts[source][1]) + 1
        return RM.Tx(source, seq[source], fee * len(ops), tuple(ops))

    mine = [[assets[(i + j) % len(assets)] for j in range(per_account)] for i in range(len(accounts))]
    yield [tx(a, [("trust", asset, LIMIT) for asset in mine[i]]) for i, a in enumerate(accounts)]
    holding = int(shape["credit_holding"])
    by_issuer: Dict[tuple, list] = {}
    for i, a in enumerate(accounts):
        for asset in mine[i]:
            by_issuer.setdefault(asset, []).append(a)
    yield [
        tx(asset[1], [("pay", h, holding, asset) for h in holders[start : start + 100]])
        for asset, holders in by_issuer.items()
        for start in range(0, len(holders), 100)
    ]
    makers = accounts[: len(accounts) // 2]
    levels = [k for k in shape["price_ladder"] if k > den]
    low, high = shape["offer_rest"]
    amount = lambda: den * rng.randrange(low // den, high // den + 1)  # noqa: E731
    yield [tx(a, [("offer", mine[i][0], None, amount(), (rng.choice(levels), den), 0)]) for i, a in enumerate(makers)]
    yield [tx(a, [("offer", None, mine[i][0], amount(), (rng.choice(levels), den), 0)]) for i, a in enumerate(makers)]


# -- the workload ---------------------------------------------------------------------------------


def plain_of(env) -> RM.Tx:
    """The plain transaction of a decoded TransactionEnvelope, signatures
    left out (``signed_by`` is what the generator recorded)."""
    from stellar_tpu.crypto.keys import PubKeyUtils

    name = PubKeyUtils.to_strkey

    def asset(a):
        if a.is_native():
            return None
        code, issuer = a.code_and_issuer()
        return (code.rstrip(b"\x00").decode("ascii"), name(issuer))

    ops = []
    for op in env.tx.operations:
        b, kind = op.body.value, op.body.type.name
        if kind == "PAYMENT":
            ops.append(("pay", name(b.destination), b.amount, asset(b.asset)))
        elif kind == "CREATE_ACCOUNT":
            ops.append(("create", name(b.destination), b.startingBalance))
        elif kind == "PATH_PAYMENT":
            ops.append(("path", name(b.destination), asset(b.sendAsset), b.sendMax, asset(b.destAsset),
                        b.destAmount, tuple(asset(a) for a in b.path)))
        elif kind == "MANAGE_OFFER":
            ops.append(("offer", asset(b.selling), asset(b.buying), b.amount, (b.price.n, b.price.d), b.offerID))
        elif kind == "CHANGE_TRUST":
            ops.append(("trust", asset(b.line), b.limit))
        elif kind == "SET_OPTIONS":
            fields = [(f, getattr(b, f)) for f in ("clearFlags", "setFlags", "masterWeight", "lowThreshold",
                                                   "medThreshold", "highThreshold") if getattr(b, f) is not None]
            if b.signer is not None:
                fields.append(("signer", (name(b.signer.pubKey), b.signer.weight)))
            ops.append(("options", tuple(fields)))
        else:
            raise ValueError(f"the plain ledger has no {kind}")
    return RM.Tx(name(env.tx.sourceAccount), env.tx.seqNum, env.tx.fee, tuple(ops))


class Workload(closes.Workload):
    planner: Optional[Planner] = None
    sets_refused = 0

    # -- set-up through closed ledgers ------------------------------------------------------
    def _close_setup(self, txs: List[RM.Tx]) -> None:
        """Close ``txs`` in sets of at most a ledger and a device batch (as
        ``multisig_closes`` sizes its set-up sets), and move the planner's
        ledger on in the order applied."""
        node = self.node
        ledgers = math.ceil(len(txs) / min(self.width, node.cfg.SIG_BATCH_MAX))
        size = math.ceil(len(txs) / ledgers)
        for start in range(0, len(txs), size):
            part = [(frame_of(node.app.network_id, tx, self.secrets), tx) for tx in txs[start : start + size]]
            ledger_data = node.ledger_data([f for f, _tx in part])
            plain = {id(f): tx for f, tx in part}
            order = [plain[id(f)] for f in ledger_data.tx_set.sort_for_apply()]
            if not ledger_data.tx_set.check_valid(node.app):
                raise RuntimeError("a set-up set did not validate")
            node.lm.close_ledger(ledger_data)
            bad = [c for c in self.tracker.close(ledger_data.ledger_seq, order) if c[0] != "txSUCCESS"]
            if bad:
                raise RuntimeError(f"set-up transactions fail on the plain ledger: {bad[:3]}")
            node.settle()

    def _set_up_state(self) -> None:
        """Issuers, trustlines, credit and the resting book."""
        import stellar_tpu.xdr as X
        from stellar_tpu.crypto.keys import SecretKey

        ctx, node = self.ctx, self.node
        shape = ctx.config["shape"]
        size = ctx.config["rehearsal"] if ctx.rehearsal else ctx.config
        n_issuers, per_account = int(size["issuers"]), int(size["lines_per_account"])
        rng = random.Random((ctx.seed << 8) ^ 0x5355)
        issuer_keys = N.keys_from_seed(ctx.seed, n_issuers, b"issuer")
        node.fund(issuer_keys, int(ctx.traffic["params"]["balance"]))
        signer_keys = N.keys_from_seed(ctx.seed, len(self.keys), b"signer")
        root = SecretKey.from_seed(node.app.network_id)
        self.secrets = {k.get_strkey_public(): k for k in [root, *self.keys, *issuer_keys, *signer_keys]}
        accounts = [k.get_strkey_public() for k in self.keys]
        issuers = [k.get_strkey_public() for k in issuer_keys]

        # the planner's ledger, from genesis through the funding ledgers
        header = node.lm.last_closed.header
        self.genesis = {root.get_strkey_public(): [node.genesis_balance, 0]}
        self.tracker = RM.Ledger(self.genesis, header.baseFee, header.baseReserve)
        for rec in node.closed:
            txs = sorted((plain_of(X.TransactionEnvelope.from_xdr(b)) for b in rec.envelopes), key=lambda t: t.seq)
            self.tracker.close(rec.seq, txs)

        for phase in set_up_phases(self.tracker, accounts, issuers, per_account, shape, node.fee, rng):
            self._close_setup(phase)

        def new_account(i: int) -> str:
            seed = hashlib.sha256(b"bench created %d %d" % (ctx.seed, i)).digest()
            return SecretKey.from_seed(seed).get_strkey_public()

        self.planner = Planner(
            self.tracker, accounts, issuers, {a: k.get_strkey_public() for a, k in zip(accounts, signer_keys)},
            new_account, node.fee, shape, ctx.seed,
        )
        self.offers_at_start = len(self.tracker.offers)
        self.whats: Dict[str, int] = {}
        self._signed: Dict[tuple, tuple] = {}
        self.sampled: Dict[str, list] = {}  # operation -> seconds of its sampled tx.apply spans, warm-up included

    def _build(self) -> list:
        if self.planner is None:
            self._set_up_state()
        node = self.node
        txs = []
        for tx, what in self.planner.plan(self.width, node.lm.current.header.ledgerSeq + len(self._sets)):
            self.whats[what] = self.whats.get(what, 0) + 1
            if tx.signed_by is not None:
                self._signed[(tx.source, tx.seq)] = tx.signed_by
            txs.append(frame_of(node.app.network_id, tx, self.secrets).envelope.to_xdr())
        self.round += 1
        return txs

    def step(self, in_window: bool) -> Reading:
        """``closes``'s step, except that a set ``check_valid`` refuses is
        counted and left out instead of ending the run: once a node has lost
        a transaction's effects (the ``drop-tx`` control; a fault of the kind
        this cell is for) a later set can hold an envelope signed by a signer
        the node never installed.  ``check`` holds the count to 0, and the
        refused set's transactions are missing from ``txhistory``."""
        if not self._sets:
            self._sets.append(self._build())
            if in_window:
                self.built_in_window += 1
        node = self.node
        txs = node.frames(self._sets.pop(0))
        ledger_data = node.ledger_data(txs)
        t0 = time.monotonic()
        if ledger_data.tx_set.check_valid(node.app):
            node.lm.externalize_value(ledger_data)
        else:
            self.sets_refused += 1
        t1 = time.monotonic()
        self.offered += len(txs)
        del ledger_data, txs
        node.settle()
        return Reading(t0, t1, self.width)

    # -- what the layer readers need ------------------------------------------------------------
    def counters(self) -> dict:
        out = super().counters()
        exchange = getattr(self.node.lm, "exchange_stats", None)
        if exchange is not None:
            out["exchange"] = dict(exchange)
        return out

    def drain_spans(self) -> list:
        spans = super().drain_spans()
        repeat = self.ctx.span
        for s in spans:
            a = s.attrs
            if not a:
                continue
            if s.name == "commit.flush" and "signer_rows" in a:
                repeat("bench.flush_rows", s.end, s.end, signer_rows=a["signer_rows"],
                       account_rows=a.get("account_rows"), trust_rows=a.get("trust_rows"),
                       offer_rows=a.get("offer_rows"))
            elif s.name == "op.exchange":
                repeat("bench.exchange", s.end, s.end, crossed=a["crossed"], pages=a["pages"], rows=a["rows"])
            elif s.name == "apply.serial" and "failed" in a:
                repeat("bench.apply_failed", s.end, s.end, failed=a["failed"])
            elif s.name == "tx.apply" and "op" in a:
                repeat("bench.tx_apply_op", s.end, s.end, op=a["op"], seconds=s.end - s.start)
                self.sampled.setdefault(a["op"], []).append(s.end - s.start)
        return spans

    def notes(self) -> dict:
        out = super().notes()
        tally = dict(self.planner.tally)
        planned = sum(tally[k] for k in KINDS)
        out.update(
            planned_txs=planned,
            shares={k: tally[k] / planned for k in KINDS},
            built_to_fail_share=tally["built_to_fail"] / planned,
            what=self.whats, tally=tally,
            offers_resting_at_start=self.offers_at_start,
            offers_resting_planned_end=len(self.tracker.offers),
            sampled_tx_apply_us={
                op: {"samples": len(v), "median": statistics.median(v) * 1e6} for op, v in sorted(self.sampled.items())
            },
        )
        out.update(getattr(self, "_found", {}))
        return out

    # -- the comparison ---------------------------------------------------------------------------
    def check(self, check) -> tuple:
        """``NodeWorkload.check`` row for row — the balances' plain arithmetic
        being the plain ledger's — then the plain ledger's own rows: every
        stored result code and every row of the four entry tables.
        -> (attempted, failed): failed also counts the closed transactions
        whose stored codes differ."""
        from benchmarks import reference as ref
        from stellar_tpu.tx.frame import TransactionFrame

        import stellar_tpu.xdr as X

        node = self.node
        node.settle()
        closed = [
            c._replace(envelopes=[X.TransactionEnvelope.from_xdr(b) for b in c.envelopes])
            for c in node.closed
        ]
        check.compare("sets_refused_by_check_valid", self.sets_refused, 0)
        inv = node.app.invariants.dump_info()
        check.compare("invariant_violations", int(inv.get("total_violations", 0)), 0)
        check.compare(
            "closes_not_invariant_checked",
            max(0, len(closed) - int(inv.get("closes_checked", 0))),
            0,
        )
        in_closed = sum(len(c.envelopes) for c in closed)
        setup_txs = in_closed - self.offered

        # durability, as the window closed (taken in ``finish``) ...
        lcl_seq, lcl_hash, closed_txs, then = self._at_close
        check.compare("durable_lcl_seq_behind", lcl_seq - (then["top"] or 0), 0, "as the last timed close returned")
        check.compare("durable_lcl_hash_differs", 0 if then["lcl"] == lcl_hash else 1, 0, f"lcl {lcl_seq}")
        check.compare(
            "closed_txs_not_yet_in_txhistory", max(0, closed_txs - then["txhistory"]), 0,
            f"{then['txhistory']} rows as the last timed close returned",
        )
        # ... and after the drain, still before the node stops
        durable = ref.durable_state(self.db_path())
        history = RM.stored_history(self.db_path())
        stored = RM.stored_state(self.db_path())
        network_id, header = node.app.network_id, node.lm.last_closed.header
        node.stop()
        missing = max(0, self.offered + setup_txs - durable["txhistory"])
        check.compare("txs_not_in_txhistory", missing, 0, f"{durable['txhistory']} rows after the drain")

        want = ref.replay_hashes(closed, self.ctx.config, node.cfg.NETWORK_PASSPHRASE, self.ctx.work)
        bad = sum(1 for c, h in zip(closed, want) if c.hash != h) + max(0, len(closed) - len(want))
        check.compare("ledger_hashes_differing", bad, 0, f"of {len(closed)} closes")

        # the plain ledger, from genesis, in the order the node applied
        signed = self._signed
        sets = []
        for c in closed:
            by_id = {}
            for env in c.envelopes:
                tx = plain_of(env)
                txid = TransactionFrame(network_id, env).get_contents_hash().hex()
                by_id[txid] = RM.Tx(tx.source, tx.seq, tx.fee, tx.ops, signed.get((tx.source, tx.seq)))
            sets.append((c.seq, by_id))
        plain = RM.Ledger(self.genesis, self.tracker.base_fee, self.tracker.base_reserve)
        found = RM.replay(plain, sets, history)
        expect = RM.state_of(plain)

        off = RM.rows_off({k: v[0] for k, v in expect["accounts"].items()}, durable["balances"])
        check.compare("balances_off_plain_arithmetic", off, 0, f"of {len(expect['accounts'])} accounts")
        check.compare(
            "result_codes_differing", found["codes_differing"], 0,
            f"of {found['txs']} closed transactions; {found['failed_at_apply']} failed at apply on the plain "
            f"ledger; {found['orders_refused']} stored orders refused",
        )
        for row, table in (("account_rows_off", "accounts"), ("trustline_rows_off", "trustlines"),
                           ("offer_rows_off", "offers"), ("signer_rows_off", "signers")):
            n = RM.rows_off(expect[table], stored[table])
            if table == "signers":
                n += stored["duplicate_signer_rows"]
            check.compare(row, n, 0, f"of {len(expect[table])} rows")
        check.compare("fee_pool_off", abs(header.feePool - plain.fee_pool), 0)
        self._found = {
            "failed_at_apply": found["failed_at_apply"],
            "failed_at_apply_share": found["failed_at_apply"] / max(1, self.offered),
            "offers_crossed": plain.claimed,
            "offers_resting_at_end": len(plain.offers),
            "trustlines_at_end": len(plain.trustlines),
            "accounts_at_end": len(plain.accounts),
        }
        return self.offered, missing + bad + found["codes_differing"]
