"""Write-back entry store buffer for the ledger-close hot path.

The reference persists every EntryFrame mutation to SQL at store time
(src/ledger/EntryFrame.h:23-79 storeAdd/storeChange/storeDelete), relying
on SQL savepoints for per-transaction rollback.  At 5000-tx ledgers that
is ~8 sqlite statements per applied transaction (~0.97 s cumulative on the
1-core bench host, PROFILE.md round-4 split) even though the only reader
of those rows before the close commits is the close itself.

This buffer makes the stores write-back instead of write-through during
``LedgerManager.close_ledger``:

- ``store_add/store_change/store_delete`` record the pending entry state
  here (and, as before, in the LedgerDelta and the decoded-entry cache);
  no SQL is issued per store.
- every keyed load / ``exists`` probe consults the buffer before SQL, and
  ``OfferFrame.load_best_offers`` pages through ``book_page`` — the
  overlay is **authoritative** for any key it holds, so apply-path reads
  observe exactly the state the reference's write-through rows would have
  shown.
- the order book is read through a **per-close view of each side**
  (``book_page``): the side's rows come from SQL once a (side, close), in
  ``(price, offerid)`` order, and stay as raw tuples; no store of the close
  changes the table, so they hold until the next flush.  The pending
  offers are indexed as they are recorded — every pending offer id, and
  the pending upserts grouped by book — and the undo log unwinds the index
  with the slots.  A page is the side's rows whose id is not pending,
  merged with the side's own pending upserts, sliced; it costs no
  ``SELECT`` and never more than the side's depth.
- SQL savepoints stay in charge of transactionality: ``Database``'s
  savepoint enter/rollback/release calls ``push_mark`` /
  ``rollback_mark`` / ``release_mark`` so a failed transaction unwinds
  its buffered writes in lockstep with its (now row-less) savepoint.
- at the end of the close the net overlay flushes as a handful of
  ``executemany`` batches (an upsert + a DELETE per entity), and
  PARANOID_MODE's delta-vs-database audit runs *after* the flush — the
  same safety net that guarded the write-through path guards this one.
  The upsert is ``INSERT … ON CONFLICT (pk) DO UPDATE`` on sqlite and
  postgres alike (``database/dialect.py`` ``upsert_sql``, PR 42): a row
  that exists is updated where it lies — it keeps its rowid, its
  primary-key index entry is not touched — and only a new key appends
  (``rowids_taken`` on ``commit.flush``: the accounts the close created).
  The pages the flush dirties wait in sqlite's page cache for the COMMIT
  (``database.py`` ``SQLITE_CACHE_KIB``: sized to hold one close's, which
  a set's width bounds, not the state's size); under sqlite's 2 MB default
  they were written to the WAL, read back and written again.
- a slot holds the key, the pending entry (None: a pending delete), the
  frame class that writes it, and for an account whether its rows of
  ``signers`` must be written with it: ``EntryFrame._record`` sets that
  where the store changed the signer list, or could not tell
  (``AccountFrame.signers_differ``); here it only stays set for the rest
  of the close and rides the undo log with its slot.  The flush rewrites
  the signer rows of the marked accounts and of no other.

Aggregate queries that cannot read through an overlay (the inflation
winners tally, ``AccountFrame.process_for_inflation``) call
``flush_through`` first: pending rows are written inside the current
savepoint (so enclosing rollbacks still undo them via SQL) and the
overlay empties while remaining consistent with outer marks.  The flush
drops the book views (the table changed under them), and so does a
rollback that crosses it (the table changes back).
"""

from __future__ import annotations

from heapq import merge
from itertools import islice
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..xdr.entries import Asset, LedgerEntry, LedgerEntryType
from ..xdr.ledger import LedgerKey

_ABSENT = object()
# in the undo log, in a key's place, where a flush emptied the overlay into SQL
_FLUSHED = object()

# overlay value: (LedgerKey, entry-or-None (None = pending delete), frame cls,
# signers_dirty: the account's signer rows must be written at the flush)
_Slot = Tuple[LedgerKey, Optional[LedgerEntry], type, bool]

# one offer of a book side as a page sees it: (price, offerid, the SQL row or
# None, the pending entry or None) — ordered by the first two, which no two
# offers of a page share
_BookItem = Tuple[float, int, Optional[tuple], Optional[LedgerEntry]]


def _asset_id(asset: Asset) -> tuple:
    v = asset.value
    return (asset.type,) if v is None else (asset.type, v.assetCode, v.issuer.value)


def book_of(selling: Asset, buying: Asset) -> tuple:
    """The hashable name of the book side that sells `selling` for `buying`."""
    return _asset_id(selling), _asset_id(buying)


class EntryStoreBuffer:
    def __init__(self):
        self.active = False
        self._overlay: Dict[bytes, _Slot] = {}
        # undo log of (key-bytes, previous-slot-or-_ABSENT); marks are
        # indices into it, one per live SQL savepoint
        self._undo: List[Tuple[bytes, Any]] = []
        self._marks: List[int] = []
        # the pending offers, indexed as they are recorded — a page of the
        # order book runs once per five offers crossed and must neither
        # rescan ~10k pending account/trust slots nor walk the offers of
        # other books: offerid -> the book of its pending upsert (None: a
        # pending delete), and book -> {offerid: pending entry}
        self._offer_book: Dict[int, Optional[tuple]] = {}
        self._book_pending: Dict[tuple, Dict[int, LedgerEntry]] = {}
        # book -> the side's rows as SQL held them when the close first
        # paged through it, in (price, offerid) order
        self._sides: Dict[tuple, List[_BookItem]] = {}
        self.n_buffered_writes = 0
        self.n_flushes = 0

    # -- lifecycle (LedgerManager.close_ledger) ----------------------------
    def activate(self) -> None:
        assert not self.active and not self._overlay and not self._marks
        self.active = True

    def deactivate(self) -> None:
        """Discard all state.  On the success path the overlay was already
        flushed; on an exception the enclosing SQL ROLLBACK is dropping the
        whole close, so pending writes are dropped with it."""
        self.active = False
        self._overlay.clear()
        self._undo.clear()
        self._marks.clear()
        self._drop_book_views()

    def _drop_book_views(self) -> None:
        self._offer_book.clear()
        self._book_pending.clear()
        self._sides.clear()

    # -- store side (EntryFrame) -------------------------------------------
    def record(self, kb: bytes, key: LedgerKey, entry: Optional[LedgerEntry],
               cls: type, signers_dirty: bool = False) -> None:
        """Pending upsert (entry) or delete (entry=None) of `key`.

        `signers_dirty`: this store changed the account's signer list (or
        nothing at hand says it did not).  A slot that was marked stays
        marked whatever later stores of the close say: the rows in SQL are
        still the ones from before the first of them.

        `entry` is the ONE shared immutable snapshot of the store
        (EntryFrame._record) — under seal-on-store it is the storing
        frame's live sealed entry, so this buffer (like the delta and the
        cache) must only read it: flush packs it to SQL rows, get() hands
        it out under the copy-before-mutate contract below, and the undo
        log restores previous snapshot objects verbatim on rollback —
        eviction/restoration of slots, never mutation of entries."""
        prev = self._overlay.get(kb, _ABSENT)
        if self._marks:
            self._undo.append((kb, prev))
        if prev is not _ABSENT and prev[3]:
            signers_dirty = True
        self._overlay[kb] = (key, entry, cls, signers_dirty)
        if key.type == LedgerEntryType.OFFER:
            self._index_offer(key.value.offerID, entry)
        self.n_buffered_writes += 1

    def _index_offer(self, offer_id: int, entry) -> None:
        """The offer's slot now holds `entry` (None: a pending delete;
        _ABSENT: no slot).  An update may have moved the offer to another
        book (MANAGE_OFFER can swap its assets): it leaves the old group."""
        was = self._offer_book.get(offer_id)
        if was is not None:
            del self._book_pending[was][offer_id]
        if entry is _ABSENT:
            self._offer_book.pop(offer_id, None)
        elif entry is None:
            self._offer_book[offer_id] = None
        else:
            o = entry.data.value
            book = book_of(o.selling, o.buying)
            self._offer_book[offer_id] = book
            self._book_pending.setdefault(book, {})[offer_id] = entry

    # -- read side ---------------------------------------------------------
    def get(self, kb: bytes) -> Tuple[bool, Optional[LedgerEntry]]:
        """(hit, pending-entry-or-None).  The returned entry is the shared
        immutable snapshot — callers must copy before mutating."""
        slot = self._overlay.get(kb, _ABSENT)
        if slot is _ABSENT:
            return False, None
        return True, slot[1]

    def book_page(
        self, book: tuple, num: int, offset: int,
        load_side: Callable[[], List[_BookItem]],
    ) -> Tuple[List[_BookItem], Optional[int], int]:
        """Offers [offset, offset + num) of `book` (``book_of``), cheapest
        first, as the close sees them: the side's SQL rows whose offer the
        overlay does not hold — it is authoritative for every pending id,
        deletes included, wherever its row lies — merged with the pending
        upserts of this book.

        `load_side()` reads the side from SQL, whole and in (price,
        offerid) order; it is called the first time the close pages through
        the book and not again before the next flush.  The order is the
        table's: the double it sorts by was computed as n / d in Python at
        write time (``OfferFrame._sql_row``), and n / d of a pending entry
        is the same double (consensus-critical).

        -> (the page; the rows `load_side` returned, None where it was not
        called; the pending upserts merged in).  Pending entries are the
        shared snapshots: copy before mutating."""
        side = self._sides.get(book)
        loaded = None
        if side is None:
            side = self._sides[book] = load_side()
            loaded = len(side)
        held = self._offer_book
        live = (item for item in side if item[1] not in held)
        pending = sorted(
            (e.data.value.price.n / e.data.value.price.d, oid, None, e)
            for oid, e in self._book_pending.get(book, {}).items()
        )
        page = list(islice(merge(live, pending), offset, offset + num))
        return page, loaded, len(pending)

    # -- savepoint integration (Database.transaction) ----------------------
    def push_mark(self) -> None:
        self._marks.append(len(self._undo))

    def release_mark(self) -> None:
        self._marks.pop()
        if not self._marks:
            # nothing outer can roll back to before this point any more
            # (the outermost BEGIN predates activation and unwinds via
            # deactivate), so the undo entries are dead weight
            self._undo.clear()

    def rollback_mark(self) -> None:
        m = self._marks.pop()
        while len(self._undo) > m:
            kb, prev = self._undo.pop()
            if kb is _FLUSHED:
                # SQL is about to roll the flushed rows back: a side read
                # since the flush holds them
                self._sides.clear()
            elif prev is _ABSENT:
                slot = self._overlay.pop(kb, None)
                if slot is not None and slot[0].type == LedgerEntryType.OFFER:
                    self._index_offer(slot[0].value.offerID, _ABSENT)
            else:
                self._overlay[kb] = prev
                if prev[0].type == LedgerEntryType.OFFER:
                    self._index_offer(prev[0].value.offerID, prev[1])

    # -- flush -------------------------------------------------------------
    def flush(self, db) -> dict:
        """Write the net overlay as batched SQL and empty it.  Inside a
        savepoint (flush_through callers) the rows land in that savepoint —
        an enclosing rollback undoes them via SQL while the undo log
        restores the overlay, keeping both planes consistent.

        -> the row counts the frame classes' ``upsert_batch`` and
        ``delete_batch`` report (``AccountFrame``: ``account_rows``,
        ``signer_rows``, ``signer_accounts``; ``TrustFrame``:
        ``trust_rows``; ``OfferFrame``: ``offer_rows``, rows written plus
        rows deleted), summed."""
        written: Dict[str, int] = {}
        if not self._overlay:
            return written
        # rows are about to land inside whatever scopes are open: give the
        # lazy (savepoint-less) buffered scopes real SQL savepoints first,
        # or an enclosing rollback could not undo these writes
        # (database.py transaction(), buffered branch)
        db.materialize_savepoints()
        if self._marks:
            for kb, slot in self._overlay.items():
                self._undo.append((kb, slot))
            self._undo.append((_FLUSHED, None))
        by_cls: Dict[type, Tuple[list, list, list]] = {}
        for key, entry, cls, signers_dirty in self._overlay.values():
            ups, dirty, dels = by_cls.setdefault(cls, ([], [], []))
            if entry is None:
                dels.append(key)
            else:
                ups.append(entry)
                dirty.append(signers_dirty)
        for cls, (ups, dirty, dels) in by_cls.items():
            counts = []
            if dels:
                counts.append(cls.delete_batch(db, dels))
            if ups:
                counts.append(cls.upsert_batch(db, ups, dirty))
            for reported in counts:
                for k, n in (reported or {}).items():
                    written[k] = written.get(k, 0) + n
        self._overlay.clear()
        self._drop_book_views()
        self.n_flushes += 1
        return written

    flush_through = flush


def store_buffer_of(db) -> EntryStoreBuffer:
    buf = getattr(db, "_store_buffer", None)
    if buf is None:
        buf = EntryStoreBuffer()
        db._store_buffer = buf
    return buf


def active_buffer(db) -> Optional[EntryStoreBuffer]:
    buf = getattr(db, "_store_buffer", None)
    return buf if buf is not None and buf.active else None
