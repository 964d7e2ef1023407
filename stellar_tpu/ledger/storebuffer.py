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
  ``OfferFrame.load_best_offers`` merges pending offers into the SQL
  order-book scan — the overlay is **authoritative** for any key it
  holds, so apply-path reads observe exactly the state the reference's
  write-through rows would have shown.
- SQL savepoints stay in charge of transactionality: ``Database``'s
  savepoint enter/rollback/release calls ``push_mark`` /
  ``rollback_mark`` / ``release_mark`` so a failed transaction unwinds
  its buffered writes in lockstep with its (now row-less) savepoint.
- at the end of the close the net overlay flushes as a handful of
  ``executemany`` batches (INSERT OR REPLACE + DELETE per entity), and
  PARANOID_MODE's delta-vs-database audit runs *after* the flush — the
  same safety net that guarded the write-through path guards this one.
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
overlay empties while remaining consistent with outer marks.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from ..xdr.entries import LedgerEntry, LedgerEntryType
from ..xdr.ledger import LedgerKey

_ABSENT = object()

# overlay value: (LedgerKey, entry-or-None (None = pending delete), frame cls,
# signers_dirty: the account's signer rows must be written at the flush)
_Slot = Tuple[LedgerKey, Optional[LedgerEntry], type, bool]


class EntryStoreBuffer:
    def __init__(self):
        self.active = False
        self._overlay: Dict[bytes, _Slot] = {}
        # undo log of (key-bytes, previous-slot-or-_ABSENT); marks are
        # indices into it, one per live SQL savepoint
        self._undo: List[Tuple[bytes, Any]] = []
        self._marks: List[int] = []
        # OFFER-typed overlay keys, maintained incrementally — the
        # order-book merge runs once per 5-offer page during crossing and
        # must not rescan ~10k pending account/trust slots each time
        self._offer_keys: set = set()
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
        self._offer_keys.clear()

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
            self._offer_keys.add(kb)
        self.n_buffered_writes += 1

    # -- read side ---------------------------------------------------------
    def get(self, kb: bytes) -> Tuple[bool, Optional[LedgerEntry]]:
        """(hit, pending-entry-or-None).  The returned entry is the shared
        immutable snapshot — callers must copy before mutating."""
        slot = self._overlay.get(kb, _ABSENT)
        if slot is _ABSENT:
            return False, None
        return True, slot[1]

    def pending_offers(self):
        """Pending offer upsert entries, plus the set of ALL offerids with
        any pending state (upsert or delete) — the SQL order-book scan must
        exclude the latter wholesale.  Iterates the OFFER key index only,
        never the full (account/trust-dominated) overlay."""
        upserts = []
        touched = set()
        for kb in self._offer_keys:
            key, entry = self._overlay[kb][:2]
            touched.add(key.value.offerID)
            if entry is not None:
                upserts.append(entry)
        return upserts, touched

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
            if prev is _ABSENT:
                self._overlay.pop(kb, None)
                self._offer_keys.discard(kb)
            else:
                self._overlay[kb] = prev
                if prev[0].type == LedgerEntryType.OFFER:
                    self._offer_keys.add(kb)

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
        self._offer_keys.clear()
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
