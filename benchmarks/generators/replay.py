"""``replay``: a fresh node catches up, in mode complete, on one checkpoint of
full ledgers from a history archive; round after round, back to back.

Set-up publishes the archive once: a plain ``SIGNATURE_BACKEND="cpu"``
standalone validator (the configuration with that one field changed) closes
one checkpoint — ledger 2 creates the accounts and raises ``maxTxSetSize``,
every later ledger up to ``CHECKPOINT_FREQUENCY - 1`` carries ``width``
single-signature native payments between distinct funded accounts, all made
from the seed — and publishes it to a file archive in the run's directory
(get / put = ``cp``, gzip as the program does).  The archive's size is the
configuration's; nothing measured decides it.

A round is what an operator who starts a validator waits for: a new node
(``SIGNATURE_BACKEND="tpu"``; new database, bucket and tmp directories,
genesis) is started, ``LedgerManager.start_catchup(mode="complete")`` is
called as ``/catchup?mode=complete`` calls it, and the node's clock is
cranked until it stands on the archive's anchor, synced.  The next round
follows at once.  The rounds' nodes share one process, so each round starts
with the process-wide verify cache emptied: every signature of a round is
one the round has never seen.

A reading is one step of the closed loop: the clock cranked until the last
closed ledger has moved — one replayed ledger where the program replays a
ledger a clock post, a whole round where its replay blocks — with the
transactions of those ledgers as its items.  A round's start (the node, the
fetch, gunzip, decode, chain verify) lies in the reading of its first
ledger, its finish in that of its last, so the window's wall has every
phase.  In warm-up a step is a whole round, so that every shape a round
dispatches has run before the window opens.  Every step has a deadline and
raises past it: nothing here can hang.

Parameters (the traffic file): ``balance`` and ``amount`` in stroops;
``step_deadline_s`` and ``publish_deadline_s``.
"""

from __future__ import annotations

import gzip
import os
import shutil
import time
from collections import Counter
from typing import Dict, List, Optional

from benchmarks import node as N
from benchmarks.stats import Reading

# what of a retired node's ``sig_backend`` block adds up over the rounds
SUMMED = (
    "device_calls", "items", "lanes", "gate_rejects", "host_assist_items", "torsion_items",
    "cpu_cutover_items", "cpu_cutover_torsion", "wedge_fallback_items",
)


def archive_of(history: dict, directory: str, writable: bool) -> dict:
    """The configuration's ``HISTORY`` with the run's archive directory put
    in, and without ``put`` / ``mkdir`` for a node that only reads."""
    out = {}
    for name, spec in history.items():
        out[name] = {
            k: v.replace("{archive}", directory)
            for k, v in spec.items()
            if writable or k == "get"
        }
    return out


def forge_archive(archive_dir: str, checkpoint: int, network_id: bytes, seq: int, index: int) -> tuple:
    """Plant a forged signature in transaction ``index`` of ledger ``seq``
    and make the archive consistent around it, as a forger would who wants
    the node to replay it: one bit of the signature flipped, the set's hash,
    that ledger's header and every header and set after it re-linked and
    re-hashed, so that the header chain verifies and every set hashes to its
    header's ``txSetHash``.  Only the signature check at apply can still
    refuse it.  -> the forged (public key, message, signature)."""
    from stellar_tpu.crypto import sha256
    from stellar_tpu.herder.txset import TxSetFrame
    from stellar_tpu.history.filetransfer import CAT_LEDGER, CAT_TRANSACTIONS, remote_checkpoint_name
    from stellar_tpu.util.xdrstream import XDRInputFileStream, XDROutputFileStream
    from stellar_tpu.xdr.ledger import LedgerHeaderHistoryEntry, TransactionHistoryEntry

    def load(category, cls):
        path = os.path.join(archive_dir, remote_checkpoint_name(category, checkpoint, ".xdr.gz"))
        plain = path[: -len(".gz")]
        with gzip.open(path, "rb") as f, open(plain, "wb") as g:
            g.write(f.read())
        with XDRInputFileStream(plain) as f:
            return path, plain, list(f.read_all(cls))

    def store(path, plain, entries):
        with XDROutputFileStream(plain) as f:
            for e in entries:
                f.write_one(e)
        with open(plain, "rb") as f, gzip.open(path, "wb") as g:
            g.write(f.read())
        os.unlink(plain)

    lpath, lplain, headers = load(CAT_LEDGER, LedgerHeaderHistoryEntry)
    tpath, tplain, sets = load(CAT_TRANSACTIONS, TransactionHistoryEntry)
    by_seq = {e.ledgerSeq: e for e in sets}
    env = by_seq[seq].txSet.txs[index]
    sig = env.signatures[0].signature
    env.signatures[0].signature = sig[:10] + bytes([sig[10] ^ 1]) + sig[11:]
    forged = None
    previous = None
    for lhe in sorted(headers, key=lambda e: e.header.ledgerSeq):
        n = lhe.header.ledgerSeq
        if n < seq:
            previous = lhe.hash
            continue
        lhe.header.previousLedgerHash = previous
        entry = by_seq.get(n)
        if entry is not None:
            entry.txSet.previousLedgerHash = previous
            frame = TxSetFrame.from_xdr_set(network_id, entry.txSet)
            lhe.header.scpValue.txSetHash = frame.get_contents_hash()
            entry.txSet.txs = [tx.envelope for tx in frame.transactions]
            if n == seq:
                tx = next(t for t in frame.transactions if t.envelope is env)
                forged = (tx.get_source_id().value, tx.get_contents_hash(), env.signatures[0].signature)
        else:
            lhe.header.scpValue.txSetHash = sha256(previous)
        lhe.hash = previous = sha256(lhe.header.to_xdr())
    store(lpath, lplain, headers)
    store(tpath, tplain, sets)
    return forged


class Round:
    """One catch-up: the fresh node, and what it did."""

    def __init__(self, index: int, work: str, app, clock, cache_entries: int):
        self.index, self.work, self.app, self.clock = index, work, app, clock
        self.lm = app.ledger_manager
        self.cache_entries_at_start = cache_entries
        self.hashes: Dict[int, bytes] = {}  # ledger -> hash as its close returned
        self.started = time.monotonic()
        self.ended: Optional[float] = None
        self.at_end: Optional[dict] = None  # what ``Workload._end_round`` found
        inner = self.lm.close_ledger

        def close_ledger(ledger_data):
            inner(ledger_data)
            self.hashes[self.lm.last_closed.header.ledgerSeq] = self.lm.last_closed.hash

        self.lm.close_ledger = close_ledger

    def lcl(self) -> int:
        return self.lm.last_closed.header.ledgerSeq

    def db_path(self) -> str:
        return self.app.config.DATABASE[len("sqlite3://") :]

    def counters(self) -> dict:
        app = self.app
        out = {"sig_backend": app.sig_backend.stats(), "close_pipeline": app.close_pipeline.stats()}
        stats = getattr(app.history_manager, "stats", None)
        if stats is not None:  # a program that counts its catch-up
            out["history"] = {k: v for k, v in stats().items() if isinstance(v, int)}
        return out


class Workload:

    def __init__(self, ctx):
        from stellar_tpu.crypto.keys import verify_cache

        self.ctx = ctx
        p = ctx.traffic["params"]
        self.width = N.width_of(ctx.config, ctx.rehearsal)
        self.n_accounts = N.accounts_of(ctx.config, ctx.rehearsal)
        if self.n_accounts < 2 * self.width:
            raise SystemExit("checkpoint-replay: a set needs 2 x width distinct accounts")
        self.step_deadline_s = float(p["step_deadline_s"])
        self.archive_dir = os.path.join(ctx.work, "archive")
        os.makedirs(self.archive_dir)
        self.cache = verify_cache()
        self.tx_count: Dict[int, int] = {}  # ledger -> transactions, as published
        self.archive_hashes: Dict[int, bytes] = {}
        self._publish(p)
        self.round: Optional[Round] = None
        self.rounds: List[Round] = []  # every round begun, warm-up's too
        self._retired: Counter = Counter()  # summed counters of the nodes let go
        self._spans: list = []
        self.replayed_in_window = 0
        self._at_close: Optional[tuple] = None

    # -- set-up: the publisher -----------------------------------------------
    def _config(self, work: str, writable: bool, **overrides):
        cfg = N.make_config(self.ctx.config, work, self.ctx.rehearsal, {**(self.ctx.traffic.get("node") or {}), **overrides})
        cfg.HISTORY = archive_of(cfg.HISTORY, self.archive_dir, writable)
        return cfg

    def _publish(self, p: dict) -> None:
        from stellar_tpu.crypto.keys import PubKeyUtils

        ctx = self.ctx
        t0 = time.time()
        work = os.path.join(ctx.work, "publisher")
        os.makedirs(work)
        cfg = self._config(work, True, SIGNATURE_BACKEND="cpu", MANUAL_CLOSE=True)
        self.anchor = cfg.CHECKPOINT_FREQUENCY - 1
        self.passphrase = cfg.NETWORK_PASSPHRASE
        node = N.Node(cfg, self.width)
        try:
            keys = N.keys_from_seed(ctx.seed, self.n_accounts)
            node.fund(keys, int(p["balance"]))
            if node.lm.last_closed.header.ledgerSeq != 2:
                raise RuntimeError("funding took more than the one ledger the checkpoint has for it")
            next_seq: dict = {}
            rnd = 0
            while node.lm.last_closed.header.ledgerSeq < self.anchor:
                order = N.permutation(ctx.seed, len(keys), rnd)
                rnd += 1
                txs = []
                for s, d in zip(order[: self.width], order[self.width : 2 * self.width]):
                    seq = next_seq.get(s, node.first_seq(keys[s]))
                    next_seq[s] = seq + 1
                    txs.append(node.payment(keys[s], seq, keys[d], int(p["amount"])))
                ledger_data = node.ledger_data(txs)
                if not ledger_data.tx_set.check_valid(node.app):
                    raise RuntimeError("a payment set did not validate")
                node.lm.close_ledger(ledger_data)
                del ledger_data, txs
                node.settle()
            for c in node.closed:
                self.tx_count[c.seq] = len(c.envelopes)
                self.archive_hashes[c.seq] = c.hash
            hm = node.app.history_manager
            if not node.clock.crank_until(lambda: hm.get_publish_success_count() > 0, float(p["publish_deadline_s"])):
                raise RuntimeError("the checkpoint was not published inside its deadline")
            self.anchor_bucket_list_hash = node.lm.last_closed.header.bucketListHash
        finally:
            node.stop()
        shutil.rmtree(work, ignore_errors=True)
        self.signatures = sum(self.tx_count.values())  # one a transaction
        PubKeyUtils.clear_verify_sig_cache()
        print(
            "set-up: %.1f s to close and publish one checkpoint: ledgers 2..%d, %d transactions"
            % (time.time() - t0, self.anchor, self.signatures),
            flush=True,
        )

    # -- a round ------------------------------------------------------------------
    def _begin_round(self) -> None:
        from stellar_tpu.crypto.keys import PubKeyUtils
        from stellar_tpu.main.application import Application
        from stellar_tpu.util.clock import REAL_TIME, VirtualClock

        self._retire()
        PubKeyUtils.clear_verify_sig_cache()
        work = os.path.join(self.ctx.work, "round-%d" % len(self.rounds))
        os.makedirs(work)
        clock = VirtualClock(REAL_TIME)
        app = Application.create(clock, self._config(work, False), new_db=True)
        app.start()
        self.round = Round(len(self.rounds), work, app, clock, len(self.cache))
        self.rounds.append(self.round)
        self.round.eager_at_start = self.cache.eager_host_verifies
        self.round.lm.start_catchup(mode="complete")

    def _end_round(self) -> None:
        """The node stands on the anchor, synced: what a fresh reader of its
        database file finds now, and what the node counts."""
        from benchmarks import reference as ref
        from benchmarks import reference_replay as RR

        rnd = self.round
        rnd.ended = time.monotonic()
        db = rnd.db_path()
        rnd.at_end = {
            "lcl": rnd.lm.last_closed.hash,
            "bucket_list_hash": rnd.app.bucket_manager.get_hash(),
            "durable": ref.durable_state(db, balances=False),
            "accounts": RR.stored_accounts(db),
            "fee_pool": rnd.lm.last_closed.header.feePool,
            "invariants": rnd.app.invariants.dump_info(),
            "counters": rnd.counters(),
            "eager": self.cache.eager_host_verifies - rnd.eager_at_start,
        }
        self.ctx.span("bench.round", rnd.started, rnd.ended)

    def _retire(self) -> None:
        """Let the round's node go, keeping its spans and its counts."""
        rnd = self.round
        if rnd is None:
            return
        self._drain(rnd)
        c = rnd.counters()
        for k in SUMMED:
            self._retired["sig_backend." + k] += c["sig_backend"].get(k, 0)
        for block in ("close_pipeline", "history"):
            for k, v in c.get(block, {}).items():
                if isinstance(v, (int, float)):
                    self._retired[block + "." + k] += v
        self._retired["applied_tx"] += self._applied(rnd)
        rnd.app.graceful_stop()
        rnd.clock.shutdown()
        shutil.rmtree(rnd.work, ignore_errors=True)
        rnd.app = rnd.lm = rnd.clock = None
        self.round = None

    @staticmethod
    def _applied(rnd: Round) -> int:
        return rnd.app.metrics.new_meter(("ledger", "transaction", "count"), "tx").count

    def _drain(self, rnd: Round) -> None:
        spans, _, dropped = rnd.app.tracer.snapshot(clear=True)
        if dropped:
            raise RuntimeError(f"the program's span ring dropped {dropped} spans")
        self._spans.extend(spans)

    def step(self, in_window: bool) -> Reading:
        from stellar_tpu.ledger.manager import LedgerState

        t0 = time.monotonic()
        deadline = t0 + self.step_deadline_s
        if self.round is None or self.round.ended is not None:
            self._begin_round()
        rnd = self.round
        before = rnd.lcl()
        target = before + 1 if in_window else self.anchor

        def there() -> bool:
            if rnd.lm.state != LedgerState.LM_CATCHING_UP_STATE:
                return True
            # the anchor's reading ends with the catch-up's finish
            return target <= rnd.lcl() < self.anchor

        while not there():
            if time.monotonic() > deadline:
                raise RuntimeError(
                    "a step passed its deadline of %.0f s at ledger %d of round %d"
                    % (self.step_deadline_s, rnd.lcl(), rnd.index)
                )
            rnd.clock.crank(block=True, max_block=0.05)
            self._drain(rnd)
        if rnd.lm.state == LedgerState.LM_SYNCED_STATE and rnd.lcl() == self.anchor:
            self._end_round()
        elif rnd.lm.state != LedgerState.LM_CATCHING_UP_STATE:
            raise RuntimeError(
                "the catch-up of round %d failed at ledger %d (state %s)" % (rnd.index, rnd.lcl(), rnd.lm.state)
            )
        t1 = time.monotonic()
        items = sum(self.tx_count[s] for s in range(before + 1, rnd.lcl() + 1))
        if in_window:
            self.replayed_in_window += items
        return Reading(t0, t1, items)

    # -- what the harness reads -----------------------------------------------------
    def counters(self) -> dict:
        """The counts of all the rounds' nodes as one node's: the blocks of
        the node of now with those of the nodes let go added in."""
        done = sum(1 for r in self.rounds if r.ended is not None)
        out = {
            "replay": {
                "rounds_begun": len(self.rounds), "rounds_done": done,
                "ledgers": done * (self.anchor - 1) + (self.round.lcl() - 1 if self.round and self.round.ended is None else 0),
                "ledgers_per_round": self.anchor - 1,
            },
            "applied_tx": self._retired["applied_tx"],
        }
        now = self.round.counters() if self.round is not None else {"sig_backend": {}, "close_pipeline": {}}
        if self.round is not None:
            out["applied_tx"] += self._applied(self.round)
        for block, values in now.items():
            merged = dict(values)
            for key, v in self._retired.items():
                b, _, k = key.partition(".")
                if b == block:
                    merged[k] = merged.get(k, 0) + v
            out[block] = merged
        # never reset, and the process's: not one node's
        out["sig_backend"]["eager_host_verifies"] = self.cache.eager_host_verifies
        return out

    def drain_spans(self) -> list:
        if self.round is not None:
            self._drain(self.round)
        out, self._spans = self._spans, []
        return out

    def notes(self) -> dict:
        return {
            "anchor": self.anchor, "ledgers_per_round": self.anchor - 1, "signatures_per_round": self.signatures,
            "rounds_begun": len(self.rounds), "rounds_done": sum(1 for r in self.rounds if r.ended is not None),
            "round_seconds": [r.ended - r.started for r in self.rounds if r.ended is not None],
        }

    def finish(self) -> None:
        """As the window closes, before anything stops or flushes the node:
        what a fresh reader finds in the database of the round under way."""
        from benchmarks import reference as ref

        rnd = self.round
        if rnd is not None:
            self._at_close = (rnd.lcl(), rnd.lm.last_closed.hash.hex(), ref.durable_state(rnd.db_path(), balances=False))

    def close(self) -> None:
        self._retire()

    def check(self, check) -> tuple:
        """Every round that ended, warm-up's too, held to the archive and to
        the plain replay of its files; every limit 0."""
        from benchmarks import reference_replay as RR

        ref = RR.replay_archive(self.archive_dir, self.anchor, self.passphrase)
        done = [r for r in self.rounds if r.ended is not None]
        check.compare("rounds_done_short_of_one", max(0, 1 - len(done)), 0, f"{len(done)} rounds ended")
        # the archive itself, by the plain reader
        for row in ("headers_off", "sets_off", "signatures_bad", "results_off", "fee_pools_off"):
            check.compare("archive_" + row, ref[row], 0, "plain replay of the archive's files")
        check.compare(
            "archive_hashes_not_the_publishers",
            sum(1 for s, h in self.archive_hashes.items() if ref["hashes"].get(s) != h), 0,
        )
        # (a) hashes, ledger for ledger and at the anchor
        bad_ledgers = bad_txs = 0
        for r in self.rounds:
            for s, h in r.hashes.items():
                if ref["hashes"].get(s) != h:
                    bad_ledgers += 1
                    bad_txs += self.tx_count[s]
        check.compare("replayed_hashes_differing", bad_ledgers, 0, f"of {sum(len(r.hashes) for r in self.rounds)} replayed ledgers")
        check.compare("anchor_hash_differs", sum(1 for r in done if r.at_end["lcl"] != ref["hashes"][self.anchor]), 0)
        check.compare(
            "bucket_list_hash_differs",
            sum(1 for r in done if r.at_end["bucket_list_hash"] != ref["bucket_list_hash"])
            + (ref["bucket_list_hash"] != self.anchor_bucket_list_hash), 0,
        )
        # (b) durability, as each round's last close returned and as the window closed
        behind = rows = 0
        for r in done:
            d = r.at_end["durable"]
            behind += (d["top"] != self.anchor) + (d["lcl"] != ref["hashes"][self.anchor].hex())
            rows += abs(d["txhistory"] - self.signatures)
        if self._at_close is not None:
            seq, lcl, d = self._at_close
            behind += (d["top"] != seq) + (d["lcl"] != lcl)
            rows += abs(d["txhistory"] - sum(self.tx_count[s] for s in range(2, seq + 1)))
        check.compare("durable_lcl_behind_or_differs", behind, 0, "as each round's last close returned")
        check.compare("txhistory_rows_off", rows, 0, "one row a transaction replayed")
        # (c) state by plain arithmetic
        off = 0
        for r in done:
            have = r.at_end["accounts"]
            off += sum(1 for k, v in ref["accounts"].items() if have.get(k) != v) + sum(1 for k in have if k not in ref["accounts"])
        check.compare("accounts_off_plain_arithmetic", off, 0, f"balance and sequence number of {len(ref['accounts'])} accounts a round")
        check.compare("fee_pool_off_plain_arithmetic", sum(1 for r in done if r.at_end["fee_pool"] != ref["fee_pool"]), 0)
        # (d) every signature verified once, somewhere
        counts_off = eager = 0
        for r in done:
            sb = r.at_end["counters"]["sig_backend"]
            verified = sb.get("items", 0) + sb.get("cpu_cutover_items", 0) + sb.get("wedge_fallback_items", 0) + r.at_end["eager"]
            counts_off += abs(verified - self.signatures)
            eager += r.at_end["eager"]
        check.compare("verify_counts_off", counts_off, 0, f"device + host + eager against {self.signatures} signatures a round; eager_host_verifies {eager}")
        # (e) a round's verdicts are its own
        check.compare("cache_entries_at_round_start", sum(r.cache_entries_at_start for r in self.rounds), 0)
        # (f) invariants
        check.compare("invariant_violations", sum(int(r.at_end["invariants"].get("total_violations", 0)) for r in done), 0)
        check.compare(
            "closes_not_invariant_checked",
            sum(max(0, self.anchor - 1 - int(r.at_end["invariants"].get("closes_checked", 0))) for r in done), 0,
        )
        return self.replayed_in_window, bad_txs
