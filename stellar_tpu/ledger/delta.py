"""LedgerDelta — nestable change-set (reference: src/ledger/LedgerDelta.{h,cpp}).

Tracks created/modified/deleted entries plus header mutation; commits merge
into the outer delta (or publish to the header at top level); rollbacks drop
the changes and flush affected entry-cache lines.  Emits LedgerEntryChanges
meta and live/dead entry lists for the bucket list.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Set

from ..xdr.base import xdr_copy
from ..xdr.entries import LedgerEntry
from .entryframe import key_bytes
from ..xdr.ledger import (
    LedgerEntryChange,
    LedgerEntryChangeType,
    LedgerHeader,
    LedgerKey,
)


class LedgerDelta:
    def __init__(
        self,
        header=None,
        db=None,
        update_last_modified: bool = True,
        outer: "LedgerDelta" = None,
    ):
        if outer is not None:
            self._outer = outer
            self._db = outer._db
            self._header_target = None
            self._previous_header = outer.header_ro()
            self.update_last_modified = outer.update_last_modified
        else:
            assert header is not None and db is not None
            self._outer = None
            self._db = db
            self._header_target = header  # committed back on commit()
            self._previous_header = header
            self.update_last_modified = update_last_modified
        # header copy is lazy: most nested deltas (one per applied tx/op)
        # only ever *read* ledgerSeq, so the private mutable copy is made
        # on first `header` access, not per delta
        self._header_local = None
        # key-xdr -> LedgerEntry (copies)
        self._new: Dict[bytes, LedgerEntry] = {}
        self._mod: Dict[bytes, LedgerEntry] = {}
        self._delete: Set[bytes] = set()
        self._key_objs: Dict[bytes, LedgerKey] = {}
        self._open = True

    # -- header ------------------------------------------------------------
    @property
    def header(self):
        """Mutable view — private copy made on first access.

        CONSTRAINT (advisor r03): because the copy is lazy, an OUTER
        delta's header must not be mutated while a nested delta is live —
        the nested copy snapshots whatever the outer header holds at the
        nested delta's FIRST header access, not at construction.  No
        current call path interleaves outer/nested header mutation (ops
        mutate only their own innermost delta's header; the close's fee
        pass, ``LedgerManager._process_fees_seq_nums``, raises ``feePool``
        on the close's own delta, where it nests nothing and runs before
        the apply loop opens its first nested delta); keep it that way or
        make the copy eager again."""
        if self._header_local is None:
            self._header_local = _copy_header(self._previous_header)
        return self._header_local

    def header_ro(self):
        """Read-only view; callers must not mutate the returned object."""
        h = self._header_local
        return h if h is not None else self._previous_header

    def get_header(self):
        return self.header

    def generate_id(self) -> int:
        self.header.idPool += 1
        return self.header.idPool

    # -- entry recording (LedgerDelta.cpp addEntry/modEntry/deleteEntry) ----
    def _remember_key(self, key: LedgerKey) -> bytes:
        kb = key_bytes(key)
        self._key_objs[kb] = key
        return kb

    def add_entry(self, frame) -> None:
        self.add_entry_snapshot(frame.get_key(), _copy_entry(frame.entry))

    def add_entry_snapshot(self, key: LedgerKey, entry: LedgerEntry) -> None:
        """Record a created entry, taking ownership of `entry` (the caller
        must not mutate it afterwards — it is shared with the entry cache
        and the store buffer as ONE immutable snapshot, and under
        seal-on-store it is also the storing frame's live entry until that
        frame CoW-unseals at its next mutation; see EntryFrame.touch).
        This delta only ever reads the object: metas (get_changes), bucket
        batches (get_live_entries), the PARANOID audit, and the invariant
        plane all pack or compare it, never write."""
        kb = self._remember_key(key)
        if kb in self._delete:
            # deleted-then-recreated == modified
            self._delete.discard(kb)
            self._mod[kb] = entry
        else:
            assert kb not in self._new and kb not in self._mod, "double create"
            self._new[kb] = entry

    def mod_entry(self, frame) -> None:
        self.mod_entry_snapshot(frame.get_key(), _copy_entry(frame.entry))

    def mod_entry_snapshot(self, key: LedgerKey, entry: LedgerEntry) -> None:
        """Record a modified entry, taking ownership of `entry` (see
        add_entry_snapshot)."""
        kb = self._remember_key(key)
        if kb in self._new:
            self._new[kb] = entry
        else:
            assert kb not in self._delete, "modifying deleted entry"
            self._mod[kb] = entry

    def delete_entry_frame(self, frame) -> None:
        self.delete_entry(frame.get_key())

    def delete_entry(self, key: LedgerKey) -> None:
        kb = self._remember_key(key)
        if kb in self._new:
            # created in this delta, then deleted: net nothing
            del self._new[kb]
        else:
            self._mod.pop(kb, None)
            self._delete.add(kb)

    # -- commit / rollback -------------------------------------------------
    def commit(self) -> None:
        assert self._open
        self._open = False
        if self._outer is not None:
            out = self._outer
            for kb, e in self._new.items():
                out._key_objs[kb] = self._key_objs[kb]
                if kb in out._delete:
                    out._delete.discard(kb)
                    out._mod[kb] = e
                else:
                    out._new[kb] = e
            for kb, e in self._mod.items():
                out._key_objs[kb] = self._key_objs[kb]
                if kb in out._new:
                    out._new[kb] = e
                else:
                    out._mod[kb] = e
            for kb in self._delete:
                out._key_objs[kb] = self._key_objs[kb]
                if kb in out._new:
                    del out._new[kb]
                else:
                    out._mod.pop(kb, None)
                    out._delete.add(kb)
            if self._header_local is not None:
                # transfer ownership — this delta is closed and will not
                # touch the object again
                out._header_local = self._header_local
        elif self._header_local is not None:
            _assign_header(self._header_target, self._header_local)

    def rollback(self) -> None:
        """Discard changes; flush entry cache for touched keys (the SQL
        rollback itself is the enclosing Database.transaction's job).
        Sealed frames whose snapshots this delta held are evicted from
        the close's identity map by FrameContext.rollback_mark in the
        same unwind (Database.transaction drives both), so no later load
        can observe the aborted scope's sealed state."""
        if not self._open:
            return
        self._open = False
        cache = getattr(self._db, "_entry_cache", None)
        if cache is not None:
            for kb in self._key_objs:
                cache.erase(kb)

    # -- outputs -----------------------------------------------------------
    def iter_changed(self):
        """Yield (LedgerKey, LedgerEntry, created) for every entry this
        delta created or modified — the invariant plane's view of the
        close (stellar_tpu/invariant/); entries are the delta's shared
        snapshots and must not be mutated by callers."""
        for kb, e in self._new.items():
            yield self._key_objs[kb], e, True
        for kb, e in self._mod.items():
            yield self._key_objs[kb], e, False

    def iter_deleted(self):
        """Yield the LedgerKey of every entry this delta deleted."""
        for kb in self._delete:
            yield self._key_objs[kb]

    def get_live_entries(self) -> List[LedgerEntry]:
        return list(self._new.values()) + list(self._mod.values())

    def get_dead_entries(self) -> List[LedgerKey]:
        return [self._key_objs[kb] for kb in self._delete]

    def get_changes(self) -> List[LedgerEntryChange]:
        changes = []
        for e in self._new.values():
            changes.append(
                LedgerEntryChange(LedgerEntryChangeType.LEDGER_ENTRY_CREATED, e)
            )
        for e in self._mod.values():
            changes.append(
                LedgerEntryChange(LedgerEntryChangeType.LEDGER_ENTRY_UPDATED, e)
            )
        for kb in self._delete:
            changes.append(
                LedgerEntryChange(
                    LedgerEntryChangeType.LEDGER_ENTRY_REMOVED, self._key_objs[kb]
                )
            )
        return changes

    def check_against_database(self, db) -> None:
        """PARANOID_MODE audit: every live entry must match the DB row
        (LedgerDelta::checkAgainstDatabase, used at LedgerManagerImpl.cpp:705)."""
        for kb, entry in {**self._new, **self._mod}.items():
            key = self._key_objs[kb]
            frame = load_fresh_entry(db, key)
            if frame is None or frame.entry.to_xdr() != entry.to_xdr():
                raise RuntimeError(f"delta-vs-database mismatch for {key}")


def load_fresh_entry(db, key):
    """Re-read one entry straight from SQL, bypassing the decoded-entry
    cache (the line is erased first, so the loader cannot serve a hit).
    The single copy of the per-type loader dispatch, shared by the
    PARANOID audit above and CacheIsConsistentWithDatabase
    (stellar_tpu/invariant/)."""
    from .accountframe import AccountFrame
    from .entryframe import key_bytes
    from .offerframe import OfferFrame
    from .trustframe import TrustFrame
    from ..xdr.entries import LedgerEntryType

    cache = getattr(db, "_entry_cache", None)
    if cache is not None:
        cache.erase(key_bytes(key))
    if key.type == LedgerEntryType.ACCOUNT:
        return AccountFrame.load_account(key.value.accountID, db)
    if key.type == LedgerEntryType.TRUSTLINE:
        return TrustFrame.load_trust_line(key.value.accountID, key.value.asset, db)
    return OfferFrame.load_offer(key.value.sellerID, key.value.offerID, db)


def _copy_entry(e: LedgerEntry) -> LedgerEntry:
    return xdr_copy(e)  # codec-driven; no serialization round-trip


_header_copies = 0


def header_copies() -> int:
    """Headers copied by any delta of this process so far; read it twice
    and subtract."""
    return _header_copies


def _copy_header(h):
    """Field-sharing copy, made lazily on first mutable `header` access —
    a payment tx's nested APPLY deltas never touch the header, so those
    pay zero copies, and the one remaining copy a close (the fee pass
    raises ``feePool`` once, by the set's sum, on the close's own delta)
    shares every subobject instead of walking the codec:
    scalars rebind, the hash fields are immutable bytes, and ``scpValue``
    is only ever whole-object ASSIGNED through a header (the herder
    composes values on its own objects; ledger/manager.py:322 assigns),
    so sharing it is safe — keep it that way.  Only the ``skipList``
    shell is copied, because bucket/manager.py writes its slots in
    place at close.  Measured ~1.9x faster than the C xdr_copy (which
    must rebuild scpValue.upgrades and the list containers).

    Every copy is counted (``header_copies``): the fee pass reports the
    copies made across its loop, so a header copied a transaction shows."""
    global _header_copies
    _header_copies += 1
    return LedgerHeader(
        h.ledgerVersion,
        h.previousLedgerHash,
        h.scpValue,
        h.txSetResultHash,
        h.bucketListHash,
        h.ledgerSeq,
        h.totalCoins,
        h.feePool,
        h.inflationSeq,
        h.idPool,
        h.baseFee,
        h.baseReserve,
        h.maxTxSetSize,
        list(h.skipList),
        h.ext,
    )


def _assign_header(dst, src) -> None:
    for f in (
        "ledgerVersion",
        "previousLedgerHash",
        "scpValue",
        "txSetResultHash",
        "bucketListHash",
        "ledgerSeq",
        "totalCoins",
        "feePool",
        "inflationSeq",
        "idPool",
        "baseFee",
        "baseReserve",
        "maxTxSetSize",
        "skipList",
    ):
        setattr(dst, f, getattr(src, f))
