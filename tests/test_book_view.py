"""The per-close view of an order-book side (ledger/storebuffer.py
``EntryStoreBuffer.book_page``, read by ``OfferFrame.load_best_offers``).

Inside a close the book is read through the close's write-back buffer: a
side's rows come from SQL once, the pending offers are indexed as they are
recorded, and a page of five is a slice of the two merged.  The contract is
equivalence, page by page: after every store, savepoint and flush of one
close, every page of every side equals

- the write-through node's (``ENTRY_WRITE_BUFFER = False``): the
  reference's ``ORDER BY price, offerid LIMIT ? OFFSET ?`` scan over rows
  written at store time;
- a brute-force merge kept here (what ``load_best_offers`` did a page until
  ISSUE 31): the side's rows as SQL has them, every offer the overlay holds
  taken out, the overlay's own upserts of this book put in, sorted.

Offers are stored frame by frame (no accounts behind them: a page reads the
``offers`` table and nothing else), so a seeded sequence can do what no
operation would — and the exchange's own walk is held to the plain ledger in
``tests/test_mixed_close.py``.
"""

import random

import pytest

import stellar_tpu.xdr as X
from stellar_tpu.ledger.delta import LedgerDelta
from stellar_tpu.ledger.offerframe import OfferFrame
from stellar_tpu.ledger.storebuffer import store_buffer_of
from stellar_tpu.ledger.trustframe import asset_from_cols
from stellar_tpu.main.application import Application
from stellar_tpu.tx import testutils as T
from stellar_tpu.util import VIRTUAL_TIME, VirtualClock
from stellar_tpu.xdr.entries import LedgerEntryType

ISSUER = T.get_account("book-view-issuer").get_public_key()
SELLERS = [T.get_account("book-view-seller-%d" % i).get_public_key() for i in range(4)]
NATIVE = X.Asset.native()
USD = X.Asset.alphanum4(b"USD", ISSUER)
EUR = X.Asset.alphanum4(b"EUR", ISSUER)
LONG = X.Asset.alphanum12(b"LONGCREDIT", ISSUER)
ASSETS = [NATIVE, USD, EUR, LONG]
SIDES = [(s, b) for s in ASSETS for b in ASSETS if s is not b]
# few prices, and two spellings of one double: equal prices are common and
# the offer id decides
PRICES = [(1, 2), (2, 4), (99, 100), (1, 1), (101, 100), (3, 2)]


class Unwind(Exception):
    """Thrown into a savepoint's scope to roll it back."""


class Pair:
    """A buffered and a write-through node holding the same ``offers``
    table, and one close under way on both: the buffered node's stores go
    to its buffer, the other's to SQL, savepoint for savepoint."""

    def __init__(self, clock, instance=236):
        self.apps = []
        for i, buffered in enumerate((True, False)):
            cfg = T.get_test_config(instance + i)
            cfg.ENTRY_WRITE_BUFFER = buffered
            self.apps.append(Application(clock, cfg, new_db=True))
        self.dbs = [app.database for app in self.apps]
        self.buf = store_buffer_of(self.dbs[0])
        self.offers = {}  # id -> (seller, selling, buying, amount, n, d): the close as it stands
        self.saved = []  # one copy of `offers` a savepoint open
        self.scopes = []  # one [scope of node 0, scope of node 1] a savepoint open
        self.next_id = 1
        self.outer = None

    # -- the close -----------------------------------------------------------
    def rest(self, offers):
        """Rows of the table before the close (written through on both)."""
        for db in self.dbs:
            with db.transaction():
                for oid, o in offers.items():
                    self._frame(oid, o).store_add(self._delta(db), db)
        self.offers.update(offers)
        self.next_id = max(self.offers, default=0) + 1

    def begin(self):
        self.outer = [db.transaction() for db in self.dbs]
        for scope in self.outer:
            scope.__enter__()
        self.buf.activate()

    def end(self):
        """Drop the close: neither node keeps anything of it."""
        while self.scopes:
            self.rollback()
        self.buf.deactivate()
        for scope in self.outer:
            scope.__exit__(Unwind, Unwind(), None)
        self.outer = None

    def shutdown(self):
        if self.outer is not None:
            self.end()
        for db in self.dbs:
            db.close()

    def _delta(self, db):
        return LedgerDelta(self.apps[0].ledger_manager.current.header, db)

    @staticmethod
    def _frame(oid, o) -> OfferFrame:
        seller, selling, buying, amount, n, d = o
        entry = X.OfferEntry(sellerID=seller, offerID=oid, selling=selling, buying=buying, amount=amount,
                             price=X.Price(n, d), flags=0, ext=0)
        return OfferFrame(X.LedgerEntry(0, X.LedgerEntryData(LedgerEntryType.OFFER, entry), 0))

    # -- stores ----------------------------------------------------------------
    def create(self, selling, buying, amount, price, seller=SELLERS[0]) -> int:
        oid, self.next_id = self.next_id, self.next_id + 1
        self.offers[oid] = (seller, selling, buying, amount, *price)
        for db in self.dbs:
            self._frame(oid, self.offers[oid]).store_add(self._delta(db), db)
        return oid

    def change(self, oid, **fields):
        seller, selling, buying, amount, n, d = self.offers[oid]
        now = dict(selling=selling, buying=buying, amount=amount, price=(n, d))
        now.update(fields)
        self.offers[oid] = (seller, now["selling"], now["buying"], now["amount"], *now["price"])
        for db in self.dbs:
            self._frame(oid, self.offers[oid]).store_change(self._delta(db), db)

    def swap(self, oid):
        """MANAGE_OFFER may swap an offer's assets: it moves to the other side's book."""
        _seller, selling, buying, _amount, _n, _d = self.offers[oid]
        self.change(oid, selling=buying, buying=selling)

    def delete(self, oid):
        o = self.offers.pop(oid)
        for db in self.dbs:
            self._frame(oid, o).store_delete(self._delta(db), db)

    # -- savepoints and the mid-close flush ----------------------------------------
    def push(self):
        self.saved.append(dict(self.offers))
        self.scopes.append([db.transaction() for db in self.dbs])
        for scope in self.scopes[-1]:
            scope.__enter__()

    def rollback(self):
        self.offers = self.saved.pop()
        for scope in self.scopes.pop():
            scope.__exit__(Unwind, Unwind(), None)

    def release(self):
        self.saved.pop()
        for scope in self.scopes.pop():
            scope.__exit__(None, None, None)

    def flush_through(self):
        """What the inflation tally does before it aggregates (the
        write-through node has nothing to flush)."""
        self.buf.flush_through(self.dbs[0])
        assert not self.buf._overlay

    # -- pages -------------------------------------------------------------------
    @staticmethod
    def _bytes(frames):
        return [f.entry.to_xdr() for f in frames]

    def brute_force_page(self, num, offset, selling, buying):
        """The per-page merge ``load_best_offers`` did until ISSUE 31,
        from the overlay scanned whole and the side read whole."""
        db = self.dbs[0]
        rows = [
            r for r in db.query_all(f"SELECT {OfferFrame._COLS} FROM offers ORDER BY price, offerid")
            if asset_from_cols(r[2], r[4], r[3]) == selling and asset_from_cols(r[5], r[7], r[6]) == buying
        ]
        touched, upserts = set(), []
        for key, entry, _cls, _dirty in self.buf._overlay.values():
            if key.type == LedgerEntryType.OFFER:
                touched.add(key.value.offerID)
                if entry is not None:
                    upserts.append(entry)
        merged = [((r[11], r[1]), r, None) for r in rows if r[1] not in touched]
        for e in upserts:
            o = e.data.value
            if o.selling == selling and o.buying == buying:
                merged.append(((o.price.n / o.price.d, o.offerID), None, e))
        merged.sort(key=lambda t: t[0])
        return [OfferFrame._row_to_frame(r) if r is not None else OfferFrame(e) for _, r, e in merged[offset : offset + num]]

    def page(self, num, offset, selling, buying):
        """One page, equal on the buffered node, the write-through node and
        the brute-force merge -> the offer ids."""
        buffered, written = (OfferFrame.load_best_offers(num, offset, selling, buying, db) for db in self.dbs)
        assert self._bytes(buffered) == self._bytes(written), (offset, num)
        assert self._bytes(buffered) == self._bytes(self.brute_force_page(num, offset, selling, buying)), (offset, num)
        return [f.get_offer_id() for f in buffered]

    def check(self, sides=SIDES):
        """Every page of five of every side, from the top and from offsets
        no walk would ask for, and the whole against the test's own book."""
        for selling, buying in sides:
            want = sorted(
                (n / d, oid) for oid, (_s, sel, buy, _a, n, d) in self.offers.items() if sel == selling and buy == buying
            )
            want = [oid for _price, oid in want]
            got, offset = [], 0
            while True:
                ids = self.page(5, offset, selling, buying)
                got += ids
                offset += 5
                if len(ids) < 5:
                    break
            assert got == want
            for num, offset in ((3, 1), (5, 3), (7, 4), (1, len(want)), (5, len(want) + 2)):
                assert self.page(num, offset, selling, buying) == want[offset : offset + num]


@pytest.fixture
def clock():
    c = VirtualClock(VIRTUAL_TIME)
    yield c
    c.shutdown()


@pytest.fixture
def pair(clock):
    p = Pair(clock)
    yield p
    p.shutdown()


def resting(rng, count, sides=SIDES):
    return {
        oid: (rng.choice(SELLERS), *rng.choice(sides), rng.randrange(1, 50) * 100, *rng.choice(PRICES))
        for oid in range(1, count + 1)
    }


# -- seeded sequences ---------------------------------------------------------------------------------

# book-heavy: two sides, so that nearly every store lands in a book that was
# paged through; all-sides: twelve, with offers moving between them
SHAPES = {"two-sides": [(USD, NATIVE), (NATIVE, USD)], "all-sides": SIDES}


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_seeded_stores_and_savepoints_page_by_page(pair, shape, seed):
    """Offers created, re-priced, resized, swapped into another book, partly
    crossed, taken and deleted, under savepoints three deep that roll back
    or release: after every step every page is the write-through node's."""
    rng = random.Random(seed * 1000 + len(shape))
    sides = SHAPES[shape]
    pair.rest(resting(rng, 40, sides))
    pair.begin()
    pair.check(sides)
    steps = dict.fromkeys(("create", "reprice", "resize", "swap", "cross", "take", "push", "rollback", "release"), 0)
    for _ in range(90):
        live = sorted(pair.offers)
        step = rng.choice(sorted(steps))
        if step == "create" or not live:
            step = "create"
            pair.create(*rng.choice(sides), rng.randrange(1, 50) * 100, rng.choice(PRICES), rng.choice(SELLERS))
        elif step == "reprice":
            pair.change(rng.choice(live), price=rng.choice(PRICES))
        elif step == "resize":
            pair.change(rng.choice(live), amount=rng.randrange(1, 50) * 100)
        elif step == "swap":
            pair.swap(rng.choice(live))
        elif step == "cross":
            # the exchange reduces the best offer of a side, or takes it
            ids = pair.page(5, 0, *rng.choice(sides))
            if ids:
                amount = pair.offers[ids[0]][3]
                if amount > 100:
                    pair.change(ids[0], amount=amount - 100)
                else:
                    pair.delete(ids[0])
        elif step == "take":
            pair.delete(rng.choice(live))
        elif step == "push":
            if len(pair.scopes) < 3:
                pair.push()
        elif pair.scopes:
            getattr(pair, step)()
        steps[step] += 1
        pair.check(sides)
    assert all(steps.values()), steps
    pair.end()
    # neither node kept anything of the close
    assert pair.dbs[0].query_all("SELECT * FROM offers ORDER BY offerid") == pair.dbs[1].query_all(
        "SELECT * FROM offers ORDER BY offerid"
    )


# -- the cases the issue names ------------------------------------------------------------------------


def walk(pair, selling, buying, stop_at=None):
    """The exchange's walk (``OfferExchange._walk_book``): pages of five
    from a cursor that steps back for each offer taken; every offer is
    taken whole.  Ends with the side, or before the offer ``stop_at``
    (cross-self).  -> the ids taken."""
    taken, offset = [], 0
    while True:
        ids = pair.page(5, offset, selling, buying)
        offset += len(ids)
        for oid in ids:
            if oid == stop_at:
                return taken
            pair.delete(oid)
            offset -= 1
            taken.append(oid)
        if len(ids) < 5:
            return taken


def case_a_whole_side_walked_and_rolled_back(pair):
    """PATH_PAYMENT_TOO_FEW_OFFERS: the walk takes every offer of the side,
    the transaction fails, and the side is as it was."""
    rng = random.Random(11)
    pair.rest(resting(rng, 64, [(USD, NATIVE), (NATIVE, USD), (EUR, NATIVE)]))
    pair.begin()
    before = sorted(pair.offers)
    side = sorted(oid for oid, o in pair.offers.items() if o[1] == USD and o[2] == NATIVE)
    for _ in range(2):  # a second failing transaction finds what the first found
        pair.push()
        assert sorted(walk(pair, USD, NATIVE)) == side and len(side) > 15
        pair.check()
        pair.rollback()
        assert sorted(pair.offers) == before
        pair.check()


def case_a_walk_that_meets_its_own_offer_and_is_rolled_back(pair):
    """OFFER_CROSS_SELF part of the way down, inside the operation's own
    savepoint, with a store before it in the transaction's that stays."""
    rng = random.Random(12)
    pair.rest(resting(rng, 48, [(USD, NATIVE), (NATIVE, USD)]))
    pair.begin()
    pair.push()  # the transaction
    made = pair.create(USD, NATIVE, 700, (1, 2))
    pair.push()  # the operation
    own = pair.page(5, 10, USD, NATIVE)[2]
    taken = walk(pair, USD, NATIVE, stop_at=own)
    assert len(taken) == 12 and made in taken
    pair.check()
    pair.rollback()
    assert made in pair.offers and own in pair.offers
    pair.check()
    pair.release()
    pair.check()


def case_a_walk_released_then_the_next_transaction_walks_on(pair):
    rng = random.Random(13)
    pair.rest(resting(rng, 48, [(USD, NATIVE), (EUR, NATIVE)]))
    pair.begin()
    pair.push()
    stop = pair.page(5, 5, USD, NATIVE)[3]
    first = walk(pair, USD, NATIVE, stop_at=stop)
    pair.change(stop, amount=1)  # the last one reduced, not taken
    pair.release()
    pair.check()
    pair.push()
    rest = walk(pair, USD, NATIVE)
    assert rest[0] == stop and not set(first) & set(rest)
    assert not pair.page(5, 0, USD, NATIVE)
    pair.release()
    pair.check()


def case_an_offer_pending_in_one_book_whose_row_is_in_another(pair):
    pair.rest({1: (SELLERS[0], USD, NATIVE, 500, 1, 1), 2: (SELLERS[1], USD, NATIVE, 500, 3, 2),
               3: (SELLERS[2], NATIVE, USD, 500, 1, 1), 4: (SELLERS[3], EUR, USD, 500, 1, 2)})
    pair.begin()
    pair.check()  # every side is read while offer 1's row is in USD/native
    pair.push()
    pair.swap(1)
    assert pair.page(5, 0, USD, NATIVE) == [2] and pair.page(5, 0, NATIVE, USD) == [1, 3]
    pair.check()
    pair.change(1, selling=EUR, buying=USD, price=(1, 2))  # and on into a third book, at 4's price: ahead of it by id
    assert pair.page(5, 0, EUR, USD) == [1, 4] and pair.page(5, 0, NATIVE, USD) == [3]
    pair.push()
    pair.delete(1)  # pending delete: in no book, and its row still hidden
    assert pair.page(5, 0, USD, NATIVE) == [2] and pair.page(5, 0, EUR, USD) == [4]
    pair.check()
    pair.rollback()
    assert pair.page(5, 0, EUR, USD) == [1, 4]
    pair.rollback()
    assert pair.page(5, 0, USD, NATIVE) == [1, 2]
    pair.check()
    # a side first read while the offer is away from its row's book
    pair.swap(3)
    pair.swap(2)
    assert pair.page(5, 0, NATIVE, USD) == [2] and pair.page(5, 0, USD, NATIVE) == [1, 3]
    pair.check()


def case_equal_prices_are_ordered_by_offer_id(pair):
    # 1/2 and 2/4 are one double; ids 1..8 rest at it, alternately spelled
    pair.rest({oid: (SELLERS[oid % 4], USD, NATIVE, 100, *((1, 2) if oid % 2 else (2, 4))) for oid in range(1, 9)})
    pair.begin()
    assert pair.page(5, 0, USD, NATIVE) == [1, 2, 3, 4, 5]
    new = pair.create(USD, NATIVE, 100, (2, 4))  # a new id at the same price: last
    pair.change(4, amount=50)  # pending, same price: keeps its place between rows 3 and 5
    pair.change(7, price=(99, 100))  # leaves the level
    cheap = pair.create(USD, NATIVE, 100, (1, 4))
    assert pair.page(5, 0, USD, NATIVE) == [cheap, 1, 2, 3, 4]
    assert pair.page(5, 5, USD, NATIVE) == [5, 6, 8, new, 7]
    pair.change(7, price=(2, 4))  # and comes back to it: by id again
    assert pair.page(5, 5, USD, NATIVE) == [5, 6, 7, 8, new]
    pair.check()


def case_a_flush_through_then_the_enclosing_rollback(pair):
    """Inflation in the middle of a close: the pending offers land in SQL
    inside the savepoint, the views go; the transaction then fails, SQL
    takes the rows back and the overlay holds them again."""
    rng = random.Random(16)
    pair.rest(resting(rng, 30, [(USD, NATIVE), (NATIVE, USD)]))
    pair.begin()
    kept = pair.create(USD, NATIVE, 100, (1, 4))  # before the savepoint: stays pending
    pair.check()
    pair.push()
    gone = pair.page(5, 0, USD, NATIVE)[1]
    pair.delete(gone)
    moved = pair.page(5, 0, NATIVE, USD)[0]
    pair.swap(moved)
    pair.check()
    loads = len(pair.buf._sides)
    pair.flush_through()
    assert not pair.buf._sides and loads
    pair.check()  # read again, from a table that now holds the close so far
    inside = pair.create(USD, NATIVE, 100, (1, 4))
    pair.change(kept, amount=70)
    pair.check()
    pair.rollback()
    assert gone in pair.offers and inside not in pair.offers and pair.offers[kept][3] == 100
    assert not pair.buf._sides  # what was read after the flush is not the table any more
    hit, pending = pair.buf.get(pair._frame(kept, pair.offers[kept]).get_key().to_xdr())
    assert hit and pending.data.value.amount == 100
    pair.check()


def case_a_flush_through_released(pair):
    rng = random.Random(17)
    pair.rest(resting(rng, 30, [(USD, NATIVE), (NATIVE, USD)]))
    pair.begin()
    pair.push()
    pair.push()
    pair.swap(pair.page(5, 0, USD, NATIVE)[0])
    pair.create(NATIVE, USD, 300, (1, 1))
    pair.check()
    pair.flush_through()
    pair.delete(pair.page(5, 0, NATIVE, USD)[0])
    pair.check()
    pair.release()
    pair.check()
    pair.release()
    # nothing can roll back over the flush any more, and the next savepoint starts clean
    pair.push()
    pair.create(USD, NATIVE, 300, (1, 1))
    pair.rollback()
    assert pair.buf._sides
    pair.check()


CASES = {name[5:]: fn for name, fn in sorted(globals().items()) if name.startswith("case_")}


@pytest.mark.parametrize("case", sorted(CASES))
def test_pages_of_one_close(pair, case):
    CASES[case](pair)
    pair.end()
    assert pair.dbs[0].query_all("SELECT * FROM offers ORDER BY offerid") == pair.dbs[1].query_all(
        "SELECT * FROM offers ORDER BY offerid"
    )


# -- what a page costs and what it hands out --------------------------------------------------------------


def select_statements(db):
    seen = []
    db._conn.set_trace_callback(lambda sql: seen.append(sql) if "FROM offers" in sql else None)
    return seen


def test_a_side_is_read_once_a_close_and_again_after_a_flush(pair):
    rng = random.Random(21)
    pair.rest(resting(rng, 60, [(USD, NATIVE), (NATIVE, USD), (EUR, NATIVE)]))
    seen = select_statements(pair.dbs[0])
    tally = {"pages": 0, "rows": 0, "side_loads": 0}
    db = pair.dbs[0]
    usd_asks = sum(1 for o in pair.offers.values() if o[1] == USD and o[2] == NATIVE)
    pair.begin()
    for offset in (0, 5, 10, 0):
        assert len(OfferFrame.load_best_offers(5, offset, USD, NATIVE, db, tally)) == 5
    # one SELECT, with no LIMIT, for four pages; `rows` is what it returned
    assert len(seen) == 1 and "LIMIT" not in seen[0] and "ORDER BY price, offerid" in seen[0]
    assert tally == {"pages": 4, "rows": usd_asks, "side_loads": 1}
    # stores, of this book and of others, and a savepoint unwound: still no SELECT;
    # a page looks at the pending upserts of its own book and of no other
    pair.push()
    pair.create(USD, NATIVE, 100, (1, 4))
    pair.create(USD, NATIVE, 100, (1, 4))
    pair.create(EUR, USD, 100, (1, 4))
    pair.delete(1)
    OfferFrame.load_best_offers(5, 0, USD, NATIVE, db, tally)
    assert tally == {"pages": 5, "rows": usd_asks + 2, "side_loads": 1}
    pair.rollback()
    OfferFrame.load_best_offers(5, 0, USD, NATIVE, db, tally)
    assert tally == {"pages": 6, "rows": usd_asks + 2, "side_loads": 1} and len(seen) == 1
    # another side: its own read
    OfferFrame.load_best_offers(5, 0, NATIVE, USD, db, tally)
    assert tally["side_loads"] == 2 and len(seen) == 2
    # an empty side is read once too
    for _ in range(2):
        assert OfferFrame.load_best_offers(5, 0, USD, EUR, db, tally) == []
    assert tally["side_loads"] == 3 and len(seen) == 3
    # the flush writes the table: one more read a side paged through after it
    pair.create(USD, NATIVE, 100, (1, 4))
    del seen[:]
    pair.flush_through()
    del seen[:]  # the flush's own statements
    OfferFrame.load_best_offers(5, 0, USD, NATIVE, db, tally)
    OfferFrame.load_best_offers(5, 5, USD, NATIVE, db, tally)
    assert tally["side_loads"] == 4 and len(seen) == 1
    # outside a close: the reference's scan, a SELECT a page
    pair.end()
    del seen[:]
    OfferFrame.load_best_offers(5, 0, USD, NATIVE, db, tally)
    OfferFrame.load_best_offers(5, 5, USD, NATIVE, db, tally)
    assert tally["side_loads"] == 4 and len(seen) == 2 and all(" LIMIT 5 OFFSET " in sql for sql in seen)


def test_every_frame_of_a_page_is_fresh(pair):
    """``cross_offer`` mutates the frame it is handed: neither a row's frame
    nor a pending entry's may be handed out twice, nor reach the buffer."""
    pair.rest({oid: (SELLERS[0], USD, NATIVE, 100 * oid, 1, 1) for oid in range(1, 5)})
    db = pair.dbs[0]
    pair.begin()
    pair.change(2, amount=250)  # a pending upsert between rows
    pair.create(USD, NATIVE, 900, (1, 1))
    first = OfferFrame.load_best_offers(5, 0, USD, NATIVE, db)
    again = OfferFrame.load_best_offers(5, 0, USD, NATIVE, db)
    assert [f.get_amount() for f in first] == [100, 250, 300, 400, 900]
    held = {id(slot[1]) for slot in pair.buf._overlay.values()}
    for a, b in zip(first, again):
        assert a is not b and a.entry is not b.entry and a.offer is not b.offer
        assert a.entry == b.entry
        assert id(a.entry) not in held and id(b.entry) not in held
        a.mut().amount = 1  # what the exchange does to the frame it crosses
        a.mut().price = X.Price(7, 1)
    assert [f.get_amount() for f in again] == [100, 250, 300, 400, 900]
    third = OfferFrame.load_best_offers(5, 0, USD, NATIVE, db)
    assert pair._bytes(third) == pair._bytes(again)
    pair.check()
