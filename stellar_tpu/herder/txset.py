"""TxSetFrame (reference: src/herder/TxSetFrame.{h,cpp}).

Canonical form: transactions sorted by full hash; contents hash =
SHA256(previousLedgerHash ‖ envelopes-in-hash-order).  Apply order re-sorts
per account by sequence number with hash-XOR randomized interleave.

**Batch-verify hot spot** (SURVEY.md §2.2): ``check_valid``/``trim_invalid``
first collect every hint-matched (pubkey, contentsHash, sig) candidate across
the whole set and flush them through the app's SigBackend (TPU or CPU) into
the shared verify cache — one device round-trip for the entire set — then run
the reference's exact eager algorithm, which now hits only cache.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from ..crypto import SHA256
from ..trace import tracer_of
from ..tx.frame import TransactionFrame
from ..xdr.ledger import TransactionSet
from ..xdr.xtypes import PublicKey


def less_than_xored(l: bytes, r: bytes, x: bytes) -> bool:
    """util/types.cpp lessThanXored."""
    v1 = bytes(a ^ b for a, b in zip(x, l))
    v2 = bytes(a ^ b for a, b in zip(x, r))
    return v1 < v2


class TxSetFrame:
    def __init__(self, previous_ledger_hash: bytes, transactions=None):
        self.previous_ledger_hash = previous_ledger_hash
        self.transactions: List[TransactionFrame] = list(transactions or [])
        self._hash: Optional[bytes] = None
        # the packed ``TransactionSet``, once someone asked for it; see
        # ``wire_bytes``
        self._wire: Optional[bytes] = None
        self._triples_memo: Optional[list] = None
        # the accounts the set can touch, and whether ``warm_asked`` has
        # counted them: both dropped with the triples when the set changes
        self._account_ids_memo: Optional[set] = None
        self._warm_counted = False
        # (ledger manager, its last closed hash) on which a full pass found
        # every account's chain of this set valid; see ``_found_valid``
        self._valid_on: Optional[tuple] = None
        # (source accounts, transactions of the longest chain) of the set
        # as the last full pass walked it; None until one has
        self.chain_shape: Optional[Tuple[int, int]] = None

    @classmethod
    def from_xdr_set(cls, network_id: bytes, xdr_set: TransactionSet) -> "TxSetFrame":
        txs = [
            TransactionFrame.make_from_wire(network_id, env) for env in xdr_set.txs
        ]
        return cls(xdr_set.previousLedgerHash, txs)

    @classmethod
    def from_wire(cls, network_id: bytes, wire: bytes) -> "TxSetFrame":
        """The set that ``wire_bytes`` packed: an equal frame (the same
        transactions in the same canonical order, so the same contents
        hash) that already carries those bytes."""
        frame = cls.from_xdr_set(network_id, TransactionSet.from_xdr(wire))
        frame._wire = wire
        return frame

    # -- canonical ordering & hash -----------------------------------------
    def sort_for_hash(self) -> None:
        txs = self.transactions
        ordered = sorted(txs, key=lambda tx: tx.get_full_hash())
        if any(a is not b for a, b in zip(ordered, txs)):
            # someone reordered the list in place: what else they did to it
            # is unknown, so the verdict goes with the order
            txs[:] = ordered
            self._valid_on = None
            self._wire = None
        self._hash = None

    def get_contents_hash(self) -> bytes:
        if self._hash is None:
            self.sort_for_hash()
            h = SHA256()
            h.add(self.previous_ledger_hash)
            for tx in self.transactions:
                h.add(tx.env_xdr())
            self._hash = h.finish()
        return self._hash

    def _changed(self) -> None:
        self._hash = None
        self._wire = None
        self._triples_memo = None
        self._account_ids_memo = None
        self._warm_counted = False
        self._valid_on = None

    def add_transaction(self, tx: TransactionFrame) -> None:
        self.transactions.append(tx)
        self._changed()

    def remove_tx(self, tx: TransactionFrame) -> None:
        try:
            self.transactions.remove(tx)
        except ValueError:
            pass
        self._changed()

    def size(self) -> int:
        return len(self.transactions)

    def to_xdr(self) -> TransactionSet:
        self.sort_for_hash()
        return TransactionSet(
            self.previous_ledger_hash, [tx.envelope for tx in self.transactions]
        )

    def wire_bytes(self) -> bytes:
        """``self.to_xdr().to_xdr()``, packed once: the bytes a ``TX_SET``
        message and the persisted SCP state carry, and what the herder's
        tx-set cache keeps of a set whose slot has closed.  The hash, a
        count and each envelope's own memoized bytes; dropped with the
        contents hash and the verdict when the set changes."""
        if self._wire is None:
            self.sort_for_hash()
            txs = self.transactions
            self._wire = b"".join(
                [self.previous_ledger_hash, len(txs).to_bytes(4, "big")]
                + [tx.env_xdr() for tx in txs]
            )
        return self._wire

    # -- apply order (TxSetFrame.cpp:93-131) -------------------------------
    def sort_for_apply(self, tally: Optional[dict] = None) -> List[TransactionFrame]:
        """Batch *d* holds every account's *d*-th transaction of the set;
        inside a batch by full hash XOR the contents hash.  ``tally``, if
        given, learns the set's ``accounts`` and ``batches``."""
        txs = sorted(self.transactions, key=lambda tx: tx.get_seq_num())
        batches: List[List[TransactionFrame]] = [[] for _ in range(4)]
        seen_count: Dict[bytes, int] = {}
        for tx in txs:
            v = seen_count.get(tx.source_bytes(), 0)
            if v >= len(batches):
                batches.extend([] for _ in range(4))
            batches[v].append(tx)
            seen_count[tx.source_bytes()] = v + 1

        # lessThanXored(l, r, x) is a lexicographic compare of l^x vs r^x,
        # which equals comparing the big-endian integers (l^x) < (r^x) —
        # so a key sort, not a comparator sort
        xh = int.from_bytes(self.get_contents_hash(), "big")
        out: List[TransactionFrame] = []
        for batch in batches:
            batch.sort(
                key=lambda tx: int.from_bytes(tx.get_full_hash(), "big") ^ xh
            )
            out.extend(batch)
        if tally is not None:
            tally["accounts"] = len(seen_count)
            tally["batches"] = max(seen_count.values(), default=0)
        return out

    def collect_account_ids(self) -> set:
        """Every account this set can touch: tx sources, op sources, and
        op targets (create/payment/path destinations, merge target,
        allow-trust trustor).  Feeds ``warm_accounts`` so big
        random-access ledgers avoid per-miss point SELECTs.  Memoized per
        set; invalidated on add_transaction/remove_tx."""
        if self._account_ids_memo is not None:
            return self._account_ids_memo
        from ..xdr.txs import OperationType as OT

        ids = set()
        for tx in self.transactions:
            ids.add(tx.get_source_id())
            for op in tx.envelope.tx.operations:
                if op.sourceAccount is not None:
                    ids.add(op.sourceAccount)
                t = op.body.type
                v = op.body.value
                if t in (OT.CREATE_ACCOUNT, OT.PAYMENT, OT.PATH_PAYMENT):
                    ids.add(v.destination)
                elif t == OT.ACCOUNT_MERGE:
                    ids.add(v)  # merge body is the destination AccountID
                elif t == OT.ALLOW_TRUST:
                    ids.add(v.trustor)
        self._account_ids_memo = ids
        return ids

    def warm_accounts(self, app, site: str) -> None:
        """Bulk-load every account the set can touch into the entry cache
        (``AccountFrame.bulk_warm_cache``: chunked IN() selects of the ids
        the cache lacks) under an ``accounts.warm`` span.  Two sites ask:
        ``collect``, before the set's signature triples are first gathered
        (``check_valid``, ``trim_invalid``, a close's own prewarm), and
        ``close``, before a close applies the set.  Whoever comes first
        pays the loads; the other finds every line there (``missed`` 0)
        unless the cache was cleared or evicted in between, and then
        reloads — nothing is skipped on the strength of a memo.  The set's
        accounts count into ``warm_asked`` once a set, whoever asks."""
        from ..ledger.accountframe import AccountFrame

        tracer = tracer_of(app)
        with tracer.span("accounts.warm", site=site) as sp:
            did = AccountFrame.bulk_warm_cache(
                app.database,
                self.collect_account_ids(),
                count_asked=not self._warm_counted,
            )
            self._warm_counted = True
            tracer.end(sp, **did)

    # -- shared validity core ----------------------------------------------
    def _collect_signature_triples(self, app) -> list:
        """Memoized per set: collection does a readonly account load per tx
        (hint-matching needs the signers) — a cache hit each, since the
        set's accounts are bulk-warmed first (``warm_accounts``, outside
        ``sig.collect``) — and close_ledger prewarms the same set
        check_valid just prewarmed.  The triples are a pure
        prefetch — the eager check_signature path re-verifies anything the
        batch missed — so a memo gone stale against DB signer changes can
        only weaken the prefetch, never change a result.  Invalidated on
        add_transaction/remove_tx.  A collection (not a memo hit) records
        ``sig.collect``."""
        if self._triples_memo is None:
            self.warm_accounts(app, "collect")
            tracer = tracer_of(app)
            with tracer.span("sig.collect", txs=len(self.transactions)) as sp:
                triples = []
                tally = {"accounts": 0}
                db = app.database
                for tx in self.transactions:
                    triples.extend(tx.candidate_signature_pairs(db, tally))
                self._triples_memo = triples
                if sp is not None:
                    # a set that hands over fewer triples than it carries
                    # signatures leaves the rest to the eager verify
                    # (``eager_host_verifies`` of the signature backend)
                    tracer.end(
                        sp,
                        signatures=sum(
                            len(tx.envelope.signatures) for tx in self.transactions
                        ),
                        triples=len(triples),
                        accounts=tally["accounts"],
                    )
        return self._triples_memo

    def _prewarm_signature_cache(self, app) -> None:
        """One SigBackend batch for the entire set (the TPU flush point)."""
        backend = getattr(app, "sig_backend", None)
        if backend is None:
            return
        triples = self._collect_signature_triples(app)
        if triples:
            backend.verify_batch(triples)

    def prewarm_signature_cache_async(self, app):
        """Start the signature-cache prewarm via the backend's async flush
        surface (SigBackend.verify_batch_async); returns a join() the
        caller must invoke before any signature check can depend on the
        warmed cache.

        Triple collection (DB reads via candidate_signature_pairs) happens
        on the CALLER's thread — sqlite connections are not shared across
        threads here.  Only the pure-compute flush (hashing + device/
        libsodium verify + at-completion cache latch, SigFlushFuture) runs
        on the worker, which lets ledger close overlap it with fee
        processing (LedgerManager.close_ledger).

        join() is bounded even through a wedged accelerator transport:
        TpuSigBackend.verify_batch carries its own DEVICE_TIMEOUT + host
        fallback (covering every call site, not just this one); a worker
        error re-raises at join()."""
        from ..crypto.sigbackend import CALLER_CLOSE

        backend = getattr(app, "sig_backend", None)
        if backend is None or not hasattr(backend, "verify_batch_async"):
            return lambda: None
        triples = self._collect_signature_triples(app)
        if not triples:
            return lambda: None
        fut = backend.verify_batch_async(triples, caller=CALLER_CLOSE)
        return fut.result

    def _account_tx_map(self) -> Dict[bytes, List[TransactionFrame]]:
        m: Dict[bytes, List[TransactionFrame]] = {}
        for tx in self.transactions:
            m.setdefault(tx.source_bytes(), []).append(tx)
        return m

    @staticmethod
    def _check_account_chain(app, txs: List[TransactionFrame]):
        """Per-account: seq chain valid + can afford total fees.
        Returns (ok, invalid_txs)."""
        txs.sort(key=lambda t: t.get_seq_num())
        invalid = []
        last_tx = None
        last_seq = 0
        tot_fee = 0
        for tx in txs:
            if not tx.check_valid(app, last_seq):
                invalid.append(tx)
                continue
            tot_fee += tx.get_fee()
            last_tx = tx
            last_seq = tx.get_seq_num()
        if last_tx is not None:
            acct = last_tx.signing_account
            if acct.get_balance() - tot_fee < acct.get_minimum_balance(
                app.ledger_manager
            ):
                return False, txs  # whole account group is bad
        return True, invalid

    # -- the verdict, remembered -------------------------------------------
    # SCP asks ``validate_value`` for one value at nomination and at every
    # ballot step, and the herder trims and checks the same frame around
    # them: ten passes a ledger over inputs that cannot have changed.  The
    # set is these exact frames until ``_changed`` or a reorder; everything
    # else a pass reads (header, accounts, signers, balances) changes only
    # when this node closes a ledger, which changes its last closed hash.
    # So a pass that found every chain valid is remembered with the ledger
    # manager it asked and that hash.  Only ``True`` is remembered: an
    # invalid set is walked again, and marks its meters again.  Another
    # node in the process (another ledger manager) does its own pass.
    def _found_valid(self, lm, lcl) -> bool:
        return self._valid_on == (lm, lcl.hash)

    def _walk_chains(self, app, lm):
        """The full pass: one signature flush for the set, then every
        account's chain.  Yields (txs, ok, invalid) an account."""
        lm.txset_validations["full"] += 1
        self._prewarm_signature_cache(app)
        chains = self._account_tx_map()
        self.chain_shape = (len(chains), max(map(len, chains.values()), default=0))
        for txs in chains.values():
            ok, invalid = self._check_account_chain(app, list(txs))
            yield txs, ok, invalid

    def check_valid(self, app) -> bool:
        """TxSetFrame.cpp:247-330."""
        tracer = tracer_of(app)
        with tracer.span("txset.validate", txs=len(self.transactions)) as sp:
            lm = app.ledger_manager
            lcl = lm.get_last_closed_ledger_header()
            if lcl.hash != self.previous_ledger_hash:
                return False
            if len(self.transactions) > lcl.header.maxTxSetSize:
                return False

            last_hash = b"\x00" * 32
            for tx in self.transactions:
                full_hash = tx.get_full_hash()
                if full_hash < last_hash:
                    return False  # not in canonical order
                last_hash = full_hash

            if self._found_valid(lm, lcl):
                lm.txset_validations["memo"] += 1
                tracer.end(sp, memo=1)
                return True

            valid = all(ok and not invalid for _txs, ok, invalid in self._walk_chains(app, lm))
            if sp is not None:
                sp.attrs["accounts"], sp.attrs["longest_chain"] = self.chain_shape
            if valid:
                self._valid_on = (lm, lcl.hash)
            return valid

    def trim_invalid(self, app) -> List[TransactionFrame]:
        """Remove invalid txs; returns the trimmed ones (TxSetFrame.cpp:190)."""
        self.sort_for_hash()
        lm = app.ledger_manager
        lcl = lm.get_last_closed_ledger_header()
        if self._found_valid(lm, lcl):
            lm.txset_validations["trim_memo"] += 1
            return []
        trimmed: List[TransactionFrame] = []
        for txs, ok, invalid in self._walk_chains(app, lm):
            for tx in invalid if ok else txs:
                trimmed.append(tx)
                self.remove_tx(tx)
        if not trimmed:
            self._valid_on = (lm, lcl.hash)
        return trimmed

    # -- surge pricing (TxSetFrame.cpp:156-186) ----------------------------
    def surge_pricing_filter(self, lm) -> None:
        max_size = lm.get_max_tx_set_size()
        if len(self.transactions) <= max_size:
            return
        account_fee: Dict[bytes, float] = {}
        for tx in self.transactions:
            r = tx.get_fee() / tx.get_min_fee(lm)
            cur = account_fee.get(tx.source_bytes(), 0.0)
            if cur == 0 or r < cur:
                account_fee[tx.source_bytes()] = r

        def surge_key(tx):
            # higher fee ratio first; ties by account id; within an account by seq
            return (
                -account_fee[tx.source_bytes()],
                tx.source_bytes(),
                tx.get_seq_num(),
            )

        ordered = sorted(self.transactions, key=surge_key)
        for tx in ordered[max_size:]:
            self.remove_tx(tx)
