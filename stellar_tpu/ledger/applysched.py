"""Conflict-partitioned transaction apply on worker threads, and the
sizing that decides whether a close uses it.

**What was read on the chip's host (PERF.md, Findings, PR 25; 13 cores,
CPython 3.12):** the shard legs run Python op bodies, so under one
interpreter lock they take turns instead of overlapping — at 5,000 tx 13
shards made a sampled transaction 18x longer than the serial loop's, and
two threads lose as well as thirteen.  ``sized_workers`` therefore
resolves ``APPLY_WORKERS = 0`` (auto) to ONE thread wherever the
interpreter serialises Python threads, and the close takes
`LedgerManager._apply_transactions`' serial loop with no partition, no
shard views, no threads and no merge replay.  The threaded plane below
runs only under an explicit ``APPLY_WORKERS >= 2`` (the tests, the chaos
scenarios and ``profile_close.py --apply-report`` pin 4) or on an
interpreter without the lock:

- **pre-pass** (`apply.partition` span): `TransactionFrame
  .static_footprint()` extracts each tx's account read/write footprint
  (source, op sources, payment/create/merge destinations).  Any tx whose
  footprint cannot be statically bounded — offers/offer-crossing, path
  payments with non-native hops, set_options with an inflation
  destination, inflation itself — classifies the whole set CONFLICTING
  and the close takes the plain serial loop, bit-exact with
  ``PARALLEL_APPLY=false`` by construction.
- **union-find** groups txs whose footprints intersect; disjoint-account
  groups are packed onto ``APPLY_WORKERS`` shards (greedy
  largest-group-first onto the lightest shard — deterministic).
- **shard planes**: each worker applies its groups against a
  ``ShardView`` — a database stand-in exposing a shard-local entry
  cache / store buffer / frame context that overlay the real (frozen)
  close planes.  Workers never touch SQL and never write a main plane;
  any out-of-footprint probe raises ``FootprintEscape`` and the whole
  set falls back to the serial loop (`apply-shard-isolation` analysis
  rule pins the discipline; tests/test_framecontext.py pins the
  bit-exactness).
- **merge** (`apply.merge` span, main thread): per-tx deltas commit into
  the close's LedgerDelta in canonical apply order, shard cache/buffer
  slots replay into the main planes (disjoint by construction), history
  rows — batch-encoded in the workers (`tx/history.transaction_rows`, the
  same call the serial loop makes once after its loop) — insert in one
  executemany, exactly like the serial loop.

The escape hatch is total: on ANY worker error the scheduler restores
the fee-pass result state (feeCharged survives, nothing else does) and
reports "not applied", so the caller's serial loop re-applies from the
exact pre-apply state.  Shard-local writes are discarded wholesale —
main planes were never touched, which is what makes the fallback safe.
"""

from __future__ import annotations

import os
import sys
import threading
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

from ..trace import NULL_TRACER
from ..util import xlog
from ..xdr.ledger import TransactionMeta
from .delta import LedgerDelta
from .framecontext import FrameContext, active_frame_context
from .storebuffer import EntryStoreBuffer, active_buffer, _ABSENT

log = xlog.logger("ApplySched")


class FootprintEscape(RuntimeError):
    """A worker touched state outside its shard's declared footprint.

    Raised by the shard planes (cache probe, buffer probe, any SQL
    surface) the moment an apply path reaches for an account — or any
    other entity, or the database itself — that the partition pre-pass
    did not assign to the shard.  The scheduler catches it, discards
    every shard, and reports the set as not-applied so the serial loop
    re-runs it; escaping is a *correct* (if slow) outcome, never a
    corruption."""


class ShardEntryCache:
    """Shard-local overlay over the frozen main entry cache.

    Reads fall through to the main cache (main-thread apply is parked
    while workers run, so main lines only move in LRU order — content
    is frozen); writes land in a shard-local dict replayed into the
    main cache at merge.  Every probe asserts the key is inside the
    shard's declared footprint."""

    def __init__(self, main, allowed: frozenset):
        self._main = main
        self._allowed = allowed
        self._local: Dict[bytes, object] = {}

    def _check(self, kb: bytes) -> None:
        if kb not in self._allowed:
            raise FootprintEscape(f"cache probe outside shard footprint: {kb[:8].hex()}")

    def peek(self, kb: bytes):
        self._check(kb)
        if kb in self._local:
            return True, self._local[kb]
        return self._main.peek(kb)

    def get(self, kb: bytes):
        from ..xdr.base import xdr_copy

        hit, e = self.peek(kb)
        return hit, (xdr_copy(e) if hit and e is not None else None)

    def put(self, kb: bytes, entry) -> None:
        from ..xdr.base import xdr_copy

        self.put_owned(kb, xdr_copy(entry) if entry is not None else None)

    def put_owned(self, kb: bytes, entry) -> None:
        # THE write-side footprint assertion: every store funnels through
        # EntryFrame._record -> cache.put_owned, so a mis-footprinted
        # mutation trips here before any shard state diverges
        self._check(kb)
        self._local[kb] = entry

    def contains(self, kb: bytes) -> bool:
        self._check(kb)
        return kb in self._local or self._main.contains(kb)

    def stored(self, kb: bytes):
        self._check(kb)
        if kb in self._local:
            return self._local[kb]
        return self._main.stored(kb)

    def erase(self, kb: bytes) -> None:
        # delta.rollback erases lines for every key the aborted scope
        # touched.  Dropping the LOCAL line is exactly right: the shard
        # buffer rolled its marks back in lockstep, so the next read
        # serves the last shard-committed slot from the buffer, or falls
        # to the untouched (pre-apply) main planes — the same state a
        # serial rollback re-reads.  Unchecked on purpose: rollback may
        # run while a FootprintEscape unwinds and must not mask it.
        self._local.pop(kb, None)

    def clear(self) -> None:
        raise FootprintEscape("cache clear inside a shard leg")


class ShardStoreBuffer(EntryStoreBuffer):
    """Shard-local overlay over the frozen main store buffer.

    Inherits the undo/mark machinery (Database.transaction drives it
    through ShardView.transaction exactly like the real buffered
    branch); only the read side chains to the main overlay and flush is
    forbidden — shard slots replay into the main buffer at merge and
    flush once, on the main thread, as always."""

    def __init__(self, main: EntryStoreBuffer, allowed: frozenset):
        super().__init__()
        self._main = main
        self._allowed = allowed
        self.active = True

    def record(self, kb, key, entry, cls, signers_dirty=False) -> None:
        if kb not in self._allowed:
            raise FootprintEscape(f"store outside shard footprint: {kb[:8].hex()}")
        super().record(kb, key, entry, cls, signers_dirty)

    def get(self, kb: bytes):
        if kb not in self._allowed:
            raise FootprintEscape(f"buffer probe outside shard footprint: {kb[:8].hex()}")
        slot = self._overlay.get(kb, _ABSENT)
        if slot is _ABSENT:
            return self._main.get(kb)
        return True, slot[1]

    def flush(self, db) -> None:
        raise FootprintEscape("flush inside a shard leg")

    flush_through = flush


class ShardView:
    """Database stand-in handed to a worker thread.

    Exposes exactly the surface the apply path resolves off a Database
    object — `_entry_cache`, `_store_buffer`, `_frame_context`,
    `_cow_entry_snapshots`, `transaction()`, `timed()` — each backed by
    a shard plane.  Every SQL method raises ``FootprintEscape``: sqlite
    connections are single-thread and the partition pre-pass guarantees
    warm caches for every in-footprint account, so a worker reaching
    SQL has, by definition, escaped its footprint."""

    def __init__(self, db, allowed: frozenset):
        from .entryframe import entry_cache_of

        self._entry_cache = ShardEntryCache(entry_cache_of(db), allowed)
        main_buf = active_buffer(db)
        assert main_buf is not None, "parallel apply requires ENTRY_WRITE_BUFFER"
        self._store_buffer = ShardStoreBuffer(main_buf, allowed)
        self._frame_context = FrameContext()
        if active_frame_context(db) is not None:
            self._frame_context.activate()
        self._cow_entry_snapshots = getattr(db, "_cow_entry_snapshots", True)

    # -- transactionality (mirrors database.py's buffered branch, minus
    # the SQL savepoint ledger: shard scopes are mark-only) --------------
    @contextmanager
    def transaction(self):
        buf = self._store_buffer
        fctx = self._frame_context if self._frame_context.active else None
        buf.push_mark()
        if fctx is not None:
            fctx.push_mark()
        try:
            yield
        except BaseException:
            buf.rollback_mark()
            if fctx is not None:
                fctx.rollback_mark()
            raise
        else:
            buf.release_mark()
            if fctx is not None:
                fctx.release_mark()

    @property
    def in_transaction(self) -> bool:
        return True

    @contextmanager
    def timed(self, op: str, entity: str):
        yield

    # -- SQL surface: forbidden in a shard leg ---------------------------
    def execute(self, *a, **k):
        raise FootprintEscape("SQL execute inside a shard leg")

    def executemany(self, *a, **k):
        raise FootprintEscape("SQL executemany inside a shard leg")

    def query_one(self, *a, **k):
        raise FootprintEscape("SQL query inside a shard leg")

    def query_all(self, *a, **k):
        raise FootprintEscape("SQL query inside a shard leg")

    def materialize_savepoints(self):
        raise FootprintEscape("savepoint materialization inside a shard leg")

    def close_view(self) -> None:
        if self._frame_context.active:
            self._frame_context.deactivate()


class _ShardLM:
    """LedgerManager facade whose `.database` is the shard view; every
    other attribute (header accessors, min-balance math, fee lookup —
    all read-only) delegates to the real manager."""

    def __init__(self, lm, shard_db: ShardView):
        self._lm = lm
        self.database = shard_db

    def __getattr__(self, name):
        return getattr(self._lm, name)


class _ShardApp:
    """Application facade for one worker: `.database` and
    `.ledger_manager` resolve to the shard planes, everything else
    (metrics, tracer, config, clock) to the real app."""

    def __init__(self, app, lm, shard_db: ShardView):
        self._app = app
        self.database = shard_db
        self.ledger_manager = _ShardLM(lm, shard_db)

    def __getattr__(self, name):
        return getattr(self._app, name)


# -- sizing -------------------------------------------------------------


def sized_workers(cfg) -> int:
    """How many interpreter threads apply a transaction set.

    An explicit ``APPLY_WORKERS`` is taken as given.  Auto (0) is sized
    from what the code can observe: worker legs run Python, so under an
    interpreter lock (no ``sys._is_gil_enabled``, or it answers true) any
    count above one only adds turn-taking — one thread; on an interpreter
    that runs Python threads side by side, the core count."""
    if cfg.APPLY_WORKERS:
        return cfg.APPLY_WORKERS
    gil_enabled = getattr(sys, "_is_gil_enabled", None)
    if gil_enabled is None or gil_enabled():
        return 1
    return os.cpu_count() or 1


# -- the scheduler -------------------------------------------------------


class ApplyScheduler:
    """Owns partition/dispatch/merge for one LedgerManager.

    ``apply()`` returns True iff the whole txset was applied in parallel
    (ledger delta, result set, history rows and close planes all updated
    exactly as the serial loop would have); False means "not touched —
    run the serial loop", which is also the answer after any escape."""

    def __init__(self, lm):
        self.lm = lm
        self.stats = {
            "total_txs": 0,
            "parallel_txs": 0,
            "conflict_fallbacks": 0,
            "escapes": 0,
            "groups": 0,
            "workers": 0,
            "closes_parallel": 0,
            "closes_serial": 0,
        }
        # last-close detail: /info, the apply.serial span's attributes and
        # profile_close.py --apply-report read it
        self.last_close: Optional[dict] = None

    def info(self) -> dict:
        """The block /info shows beside the verifier's: how the node's
        closes were applied."""
        return {
            "workers": sized_workers(self.lm.app.config),
            "closes_parallel": self.stats["closes_parallel"],
            "closes_serial": self.stats["closes_serial"],
            "reason": (self.last_close or {}).get("reason"),
        }

    def _serial(self, reason: str) -> bool:
        """Record that this set goes to the caller's serial loop."""
        self.stats["closes_serial"] += 1
        self.last_close = {"mode": "serial", "reason": reason}
        return False

    # -- partition -------------------------------------------------------
    def _partition(self, txs) -> Optional[List[List[Tuple[int, object]]]]:
        """Disjoint-account groups of (canonical_index, tx), or None if
        any tx's footprint is unboundable (CONFLICTING set)."""
        footprints = []
        for tx in txs:
            fp = tx.static_footprint()
            if fp is None:
                return None
            footprints.append(sorted(fp))
        parent: Dict[bytes, bytes] = {}

        def find(x: bytes) -> bytes:
            root = x
            while parent[root] != root:
                root = parent[root]
            while parent[x] != root:  # path compression
                parent[x], x = root, parent[x]
            return root

        for kbs in footprints:
            first = kbs[0]
            if first not in parent:
                parent[first] = first
            r = find(first)
            for kb in kbs[1:]:
                if kb not in parent:
                    parent[kb] = r
                else:
                    parent[find(kb)] = r
        groups: Dict[bytes, List[Tuple[int, object]]] = {}
        for idx, (tx, kbs) in enumerate(zip(txs, footprints)):
            groups.setdefault(find(kbs[0]), []).append((idx, tx))
        # dict insertion order == first-tx canonical order: deterministic
        return list(groups.values())

    def _assign(self, groups, n_shards: int):
        """Greedy bin-pack: groups largest-first onto the lightest shard
        (ties break to the lowest shard index) — deterministic, and the
        classic 4/3-approximation is plenty for ~uniform payment sets."""
        order = sorted(range(len(groups)), key=lambda g: (-len(groups[g]), g))
        shards: List[List[int]] = [[] for _ in range(n_shards)]
        load = [0] * n_shards
        for g in order:
            s = min(range(n_shards), key=lambda i: (load[i], i))
            shards[s].append(g)
            load[s] += len(groups[g])
        return [s for s in shards if s]

    # -- worker leg ------------------------------------------------------
    def _run_shard(self, shard_db, shard_app, jobs, ledger_delta, seq, tx_timer, tracer, parent, outcomes, rows_out, errors):  # analysis: shard-leg
        """Apply this shard's groups against its shard planes.

        Receives every plane it may touch as an explicit parameter —
        the apply-shard-isolation rule forbids this leg from reaching
        a `.database` attribute or any SQL surface, so a refactor that
        re-introduces a main-plane dependency fails analysis, not
        production.  Mirrors the serial loop body except that per-tx
        deltas are NOT committed here: they queue for the canonical-
        order merge on the main thread.  ``parent`` is the span open on
        the main thread (``close.apply``): this thread's spans name it."""
        from ..tx.frame import TX_SAMPLE_STRIDE
        from ..tx.history import transaction_rows
        from ..xdr.txs import TransactionResultCode

        try:
            with tracer.span(
                "apply.group",
                parent=parent,
                groups=len(jobs),
                txs=sum(len(g) for g in jobs),
            ):
                done = []
                skip = TX_SAMPLE_STRIDE - 1
                for group in jobs:
                    for idx, tx in group:
                        # one transaction in TX_SAMPLE_STRIDE records
                        # tx.apply and its children; the others get the
                        # no-op tracer
                        tx_tracer = NULL_TRACER if idx & skip else tracer
                        with tx_tracer.span("tx.apply", index=idx), tx_timer.time_scope():
                            delta = LedgerDelta(outer=ledger_delta)
                            # nested deltas inherit _db from their outer: point
                            # the whole chain at the shard planes so rollbacks
                            # erase shard cache lines, never main ones
                            delta._db = shard_db
                            meta = TransactionMeta(0, [])
                            try:
                                ok = tx.apply(delta, shard_app, meta, tx_tracer)
                                if not ok:
                                    assert not delta.get_changes()
                            except FootprintEscape:
                                raise
                            except Exception as e:  # serial-loop parity
                                log.error("exception during tx apply: %s", e)
                                tx.set_result_code(
                                    TransactionResultCode.txINTERNAL_ERROR
                                )
                                ok = False
                        outcomes[idx] = (ok, delta)
                        done.append((idx, tx, meta))
                # one encode call for the leg's rows, as the serial loop's
                blobs = [
                    (
                        idx + 1,
                        tx.get_contents_hash(),
                        tx.env_xdr(),
                        tx.get_result_pair().to_xdr(),
                        meta.to_xdr(),
                    )
                    for idx, tx, meta in done
                ]
                for row in transaction_rows(seq, blobs):
                    rows_out[row[2] - 1] = row
        except BaseException as e:
            errors.append(e)

    # -- fallback --------------------------------------------------------
    def _restore_for_serial(self, txs, fees, shard_views) -> None:
        """Undo the only main-visible worker effects — per-tx result
        mutations — and drop the shard planes.  feeCharged is restored
        to the fee pass's exact value (including its take-all-they-have
        adjustment), so the serial re-apply starts from the precise
        pre-apply state."""
        for tx, fee in zip(txs, fees):
            tx.reset_results()
            tx.result.feeCharged = fee
        for sv in shard_views:
            sv.close_view()

    # -- entry point -----------------------------------------------------
    def apply(self, txs, ledger_delta, tx_result_set) -> bool:
        from ..tx import history as tx_history

        lm = self.lm
        self.stats["total_txs"] += len(txs)
        cfg = lm.app.config
        if not cfg.PARALLEL_APPLY:
            return self._serial("parallel-apply-off")
        workers = sized_workers(cfg)
        if workers <= 1:
            # the shipped default under an interpreter lock: nothing of
            # the plane below runs, not even the partition
            return self._serial("one-worker")
        db = lm.database
        if active_buffer(db) is None:
            # per-shard writes merge through the store buffer; without it
            # every store is a (single-threaded) SQL write — stay serial
            return self._serial("no-store-buffer")
        if not txs:
            return self._serial("empty-txset")
        tracer = lm.app.tracer
        with tracer.span("apply.partition", txs=len(txs)):
            groups = self._partition(txs)
        if groups is None:
            self.stats["conflict_fallbacks"] += 1
            return self._serial("conflicting-txset")
        if len(groups) < 2:
            return self._serial("single-group")
        workers = min(workers, len(groups))
        seq = lm.current.header.ledgerSeq
        fees = [tx.result.feeCharged for tx in txs]
        # the set-up before any shard runs: as long as apply.partition at
        # 5,000 tx (it walks every footprint again)
        with tracer.span("apply.shards", groups=len(groups), workers=workers):
            shard_groups = self._assign(groups, workers)
            shard_views = [
                ShardView(db, frozenset().union(*(
                    (kb for _i, tx in groups[g] for kb in tx.static_footprint())
                    for g in sg
                )))
                for sg in shard_groups
            ]
        outcomes: dict = {}
        rows_out: dict = {}
        errors: list = []
        threads = []
        for sv, sg in zip(shard_views, shard_groups):
            shard_app = _ShardApp(lm.app, lm, sv)
            t = threading.Thread(
                target=self._run_shard,
                args=(
                    sv,
                    shard_app,
                    [groups[g] for g in sg],
                    ledger_delta,
                    seq,
                    lm._tx_apply_timer,
                    tracer,
                    tracer.current(),
                    outcomes,
                    rows_out,
                    errors,
                ),
                name=f"apply-shard-{len(threads)}",
                daemon=True,
            )
            threads.append(t)
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        if errors or any(i not in outcomes for i in range(len(txs))):
            for e in errors:
                if isinstance(e, FootprintEscape):
                    log.info("parallel apply escaped to serial: %s", e)
                else:
                    log.error("parallel apply worker failed: %r", e)
            self._restore_for_serial(txs, fees, shard_views)
            self.stats["escapes"] += 1
            self.stats["conflict_fallbacks"] += 1
            return self._serial("escape")

        with tracer.span(
            "apply.merge", shards=len(shard_views), groups=len(groups)
        ):
            # validation BEFORE any commit: an allowed op must never have
            # touched the header (fee pool / idPool / inflation are all
            # CONFLICTING classifications) — a local header here means the
            # footprint pre-pass mis-classified, so discard everything
            # and let the serial loop produce the truth
            if any(
                outcomes[i][1]._header_local is not None
                for i in range(len(txs))
            ):
                log.error("parallel apply: shard delta mutated the header")
                self._restore_for_serial(txs, fees, shard_views)
                self.stats["escapes"] += 1
                self.stats["conflict_fallbacks"] += 1
                return self._serial("header-escape")
            rows = []
            for i, tx in enumerate(txs):
                ok, delta = outcomes[i]
                if ok:
                    delta.commit()
                lm._tx_count_meter.mark()
                tx_result_set.results.append(tx.get_result_pair())
                rows.append(rows_out[i])
            main_cache = db._entry_cache
            main_buf = active_buffer(db)
            main_fctx = active_frame_context(db)
            for sv in shard_views:
                local = sv._entry_cache._local
                for kb, entry in local.items():
                    main_cache.put_owned(kb, entry)
                    if main_fctx is not None:
                        # the main context may still map a pre-apply frame
                        # (fee pass adopted it); shard stores superseded it,
                        # so evict — the next signing load re-copies the
                        # merged cache line, exactly like a cold close
                        main_fctx.evict(kb)
                for kb, slot in sv._store_buffer._overlay.items():
                    # a mark the fee pass left on the main slot outlives
                    # the shard's (EntryStoreBuffer.record: once set, set)
                    main_buf.record(kb, *slot)
                    if kb not in local:
                        # a later transaction of the shard failed and its
                        # rollback erased the shard's line for an account an
                        # earlier one had stored: the slot holds the truth
                        # and the main line is the pre-apply one — drop it,
                        # as the serial loop's rollback does
                        main_cache.erase(kb)
                        if main_fctx is not None:
                            main_fctx.evict(kb)
                sv.close_view()
            with tracer.span("apply.rows", rows=len(rows)):
                tx_history.insert_transaction_rows(lm.database, rows)

        self.stats["parallel_txs"] += len(txs)
        self.stats["groups"] += len(groups)
        self.stats["workers"] = len(shard_views)
        self.stats["closes_parallel"] += 1
        self.last_close = {
            "mode": "parallel",
            "txs": len(txs),
            "groups": len(groups),
            "workers": len(shard_views),
            "group_sizes": [len(g) for g in groups],
            "shard_txs": [
                sum(len(groups[g]) for g in sg) for sg in shard_groups
            ],
        }
        return True


def apply_scheduler_of(lm) -> ApplyScheduler:
    sched = getattr(lm, "_apply_sched", None)
    if sched is None:
        sched = ApplyScheduler(lm)
        lm._apply_sched = sched
    return sched
