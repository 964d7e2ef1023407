"""``state_closes``: consecutive full sets, half of them CREATE_ACCOUNT, closed
by a validator that joined a network of residents by catch-up minimal.

Set-up, each part printed with its seconds:

1. *keys* — every resident's public key from the seed (``node.keys_from_seed``'s
   rule: key ``i`` is ed25519 of ``sha256("bench acct <seed> <i>")``), spread over
   the host's cores by child processes that never import JAX; a secret is
   derived again only when its resident is drawn as a source.
2. *archive* — the residents as fixed-width account entries (no signers, no
   home domain) laid down as arrays into bucket files, record-marked XDR sorted
   by key and gzipped by ``gzip`` as the program publishes; the
   ``HistoryArchiveState`` that names them; the anchor checkpoint's ledger file
   holding the anchor header, whose ``bucketListHash`` is that state's and whose
   ``totalCoins`` is the residents' balances plus its fee pool.  Where a resident
   lies is one rule (``level_of_age``): its last-modified ledger is a seeded
   uniform draw from ledger 1 to the newest ledger levels 0-4 no longer hold, and
   it lies in the ``curr`` of the level that holds entries of that age; levels
   0-4, every ``snap`` and every ``next`` are empty at the anchor.
3. *catch-up* — a fresh ``SIGNATURE_BACKEND="tpu"`` node, the archive's ``get``
   alone, ``LedgerManager.start_catchup(mode="minimal")`` as ``/catchup?mode=
   minimal`` calls it, its clock cranked until it stands on the anchor, synced;
   past ``catchup_deadline_s`` the step raises.  No side loader: the state
   reaches SQL and the bucket list through ``CatchupStateMachine`` alone.
4. *copy* — the node's database (``sqlite3``'s backup) and bucket directory
   copied for the plain ``cpu`` node of the check.
5. *sets* — built and signed as ``closes`` builds them: 7,500 residents a set
   drawn uniformly without replacement from all of them (at the configuration's
   width), one third sources of a CREATE_ACCOUNT of a new key from the seed, one
   third sources and one third destinations of a native PAYMENT.

A reading is one close, as in ``closes``: ``check_valid`` + ``externalize_value``.

Parameters (the traffic file): ``anchor_ledger``; ``resident_balance``,
``create_balance``, ``amount`` in stroops; ``fee_pool`` of the anchor header;
``catchup_deadline_s``; ``floor_close_s_per_tx`` as in ``closes``; ``sample``
(untouched residents compared after the window); ``rehearsal_sets``.
"""

from __future__ import annotations

import ctypes
import hashlib
import math
import os
import shutil
import sqlite3
import struct
import subprocess
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks import node as N
from benchmarks.stats import Reading

NUM_LEVELS = 11  # the bucket list's
ENTRY_BYTES = 96  # a BucketEntry of an account with no signers and no home domain
FRAME_BYTES = 4 + ENTRY_BYTES
# offsets inside the entry: lastModifiedLedgerSeq, the key, balance, seqNum
AT_MODIFIED, AT_KEY, AT_BALANCE, AT_SEQ = 4, 16, 48, 56
PARALLEL_KEYS = 20000  # fewer than this are derived in this process


# -- keys ------------------------------------------------------------------------


def key_seed(label: bytes, seed: int, i: int) -> bytes:
    return hashlib.sha256(b"bench %s %d %d" % (label, seed, i)).digest()


def derive_public_keys(label: bytes, seed: int, lo: int, hi: int) -> bytes:
    """The public keys ``lo..hi-1`` of ``node.keys_from_seed(seed, n, label)``,
    32 bytes each, by libsodium alone (no program import, no JAX)."""
    from benchmarks.reference import _sodium

    fn = _sodium().crypto_sign_seed_keypair
    pk, sk = ctypes.create_string_buffer(32), ctypes.create_string_buffer(64)
    out = bytearray()
    for i in range(lo, hi):
        fn(pk, sk, key_seed(label, seed, i))
        out += pk.raw
    return bytes(out)


def public_keys(label: bytes, seed: int, lo: int, hi: int, root: str, scratch: str) -> np.ndarray:
    """Keys ``lo..hi-1`` as (hi - lo, 32) uint8.  From ``PARALLEL_KEYS`` up the
    range is split over the cores this process may use, one child a share
    (``python -m`` of this module: it imports neither the program nor JAX, so
    it cannot reach the chip its parent holds)."""
    n = hi - lo
    if n < PARALLEL_KEYS:
        raw = derive_public_keys(label, seed, lo, hi)
    else:
        workers = max(1, min(len(os.sched_getaffinity(0)), 16))
        step = -(-n // workers)
        os.makedirs(scratch, exist_ok=True)
        jobs = []
        for w, start in enumerate(range(lo, hi, step)):
            out = os.path.join(scratch, "keys-%s-%d" % (label.decode(), w))
            cmd = [sys.executable, "-m", "benchmarks.generators.state_closes", "keys",
                   label.decode(), str(seed), str(start), str(min(hi, start + step)), out]
            jobs.append((subprocess.Popen(cmd, cwd=root), out))
        parts = []
        for child, out in jobs:
            if child.wait() != 0:
                raise RuntimeError("a key-derivation child failed")
            with open(out, "rb") as f:
                parts.append(f.read())
            os.unlink(out)
        raw = b"".join(parts)
    if len(raw) != 32 * n:
        raise RuntimeError("key derivation came back short")
    return np.frombuffer(raw, np.uint8).reshape(n, 32)


# -- the archive --------------------------------------------------------------------


def level_bounds() -> List[int]:
    """``bounds[l]``: the ledgers levels 0..l hold together (level l holds
    4^(l+1) ledgers of churn)."""
    out, total = [], 0
    for level in range(NUM_LEVELS):
        total += 4 ** (level + 1)
        out.append(total)
    return out


def level_of_age(ages: np.ndarray) -> np.ndarray:
    """The bucket level that holds an entry last modified ``ages`` ledgers
    before the ledger after the anchor: the first whose levels-so-far span
    reaches that far back, the deepest for anything older."""
    return np.minimum(np.searchsorted(np.array(level_bounds()), ages, side="left"), NUM_LEVELS - 1)


def entry_template() -> bytes:
    """One resident's BucketEntry from the program's own codec, with its four
    varying fields zero; their offsets are checked, not trusted."""
    import stellar_tpu.xdr as X
    from stellar_tpu.ledger.accountframe import AccountFrame
    from stellar_tpu.xdr.ledger import BucketEntry, BucketEntryType

    probe = bytes(range(1, 33))
    frame = AccountFrame(account_id=X.PublicKey.from_ed25519(probe))
    frame.mut().balance = 0x0102030405060708
    frame.mut().seqNum = 0x1112131415161718
    frame.entry.lastModifiedLedgerSeq = 0x21222324
    body = BucketEntry(BucketEntryType.LIVEENTRY, frame.entry).to_xdr()
    want = {
        AT_MODIFIED: struct.pack(">I", 0x21222324), AT_KEY: probe,
        AT_BALANCE: struct.pack(">q", 0x0102030405060708), AT_SEQ: struct.pack(">Q", 0x1112131415161718),
    }
    if len(body) != ENTRY_BYTES or any(body[at : at + len(v)] != v for at, v in want.items()):
        raise RuntimeError("the program's account entry is not laid out as this generator lays it")
    out = bytearray(body)
    for at, v in want.items():
        out[at : at + len(v)] = bytes(len(v))
    return bytes(out)


def big_endian(values: np.ndarray, width: int) -> np.ndarray:
    """(n,) unsigned -> (n, width) uint8, most significant byte first."""
    return values.astype(">u%d" % width).view(np.uint8).reshape(len(values), width)


def bucket_hash(frames: np.ndarray) -> bytes:
    """The bucket's content hash: SHA-256 over the SHA-256 of each frame as
    written (``stellar_tpu/bucket/hashplane.py``'s rule, computed here by
    ``hashlib``)."""
    outer = hashlib.sha256()
    sha = hashlib.sha256
    data = frames.tobytes()
    for at in range(0, len(data), FRAME_BYTES):
        outer.update(sha(data[at : at + FRAME_BYTES]).digest())
    return outer.digest()


def write_archive(archive_dir: str, seed: int, pubs: np.ndarray, width: int, p: dict) -> dict:
    """The synthesised anchor checkpoint.  -> what the generator keeps of it:
    ``anchor``, ``modified`` (each resident's last-modified ledger, which its
    sequence number starts from), ``bucket_list_hash``, ``header_hash``,
    ``levels`` {level: residents}."""
    from stellar_tpu.crypto import sha256
    from stellar_tpu.history.archive import (
        WELL_KNOWN_PATH, HistoryArchiveState, remote_bucket_name, remote_checkpoint_name,
    )
    from stellar_tpu.history.filetransfer import CAT_LEDGER
    from stellar_tpu.util.xdrstream import XDROutputFileStream
    from stellar_tpu.xdr.ledger import LedgerHeader, LedgerHeaderHistoryEntry, StellarValue

    n = len(pubs)
    anchor = int(p["anchor_ledger"])
    balance = int(p["resident_balance"])
    shallow = level_bounds()[4]  # levels 0-4 hold the newest 1,364 ledgers: empty at the anchor
    rng = np.random.default_rng([seed, 0x57A7E])
    modified = rng.integers(1, anchor + 1 - shallow, size=n, dtype=np.int64)
    levels = level_of_age(anchor + 1 - modified)
    template = np.frombuffer(entry_template(), np.uint8)
    mark = np.frombuffer(struct.pack(">I", 0x80000000 | ENTRY_BYTES), np.uint8)
    state = HistoryArchiveState(anchor)
    gzips, counts = [], {}
    for level in range(NUM_LEVELS):
        who = np.flatnonzero(levels == level)
        if not len(who):
            continue
        # a bucket is sorted by entry identity: for accounts, the key's bytes
        who = who[np.argsort(pubs[who].view("S32").ravel(), kind="stable")]
        frames = np.empty((len(who), FRAME_BYTES), np.uint8)
        frames[:, :4] = mark
        frames[:, 4:] = template
        frames[:, 4 + AT_MODIFIED : 4 + AT_MODIFIED + 4] = big_endian(modified[who], 4)
        frames[:, 4 + AT_KEY : 4 + AT_KEY + 32] = pubs[who]
        frames[:, 4 + AT_BALANCE : 4 + AT_BALANCE + 8] = big_endian(np.full(len(who), balance, np.int64), 8)
        frames[:, 4 + AT_SEQ : 4 + AT_SEQ + 8] = big_endian(modified[who] << 32, 8)
        h = bucket_hash(frames)
        state.current_buckets[level].curr = h
        counts[level] = int(len(who))
        path = os.path.join(archive_dir, remote_bucket_name(h))[: -len(".gz")]
        os.makedirs(os.path.dirname(path), exist_ok=True)
        frames.tofile(path)
        gzips.append(subprocess.Popen(["gzip", "-f", path]))
    header = LedgerHeader(
        previousLedgerHash=sha256(b"state anchor previous %d" % seed),
        scpValue=StellarValue(sha256(b"state anchor txset %d" % seed), anchor * 5, [], 0),
        txSetResultHash=sha256(b"state anchor results %d" % seed),
        bucketListHash=state.bucket_list_hash(),
        ledgerSeq=anchor,
        totalCoins=n * balance + int(p["fee_pool"]),
        feePool=int(p["fee_pool"]),
        maxTxSetSize=width,
    )
    header_hash = sha256(header.to_xdr())
    path = os.path.join(archive_dir, remote_checkpoint_name(CAT_LEDGER, anchor, ".xdr"))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with XDROutputFileStream(path) as f:
        f.write_one(LedgerHeaderHistoryEntry(header_hash, header, 0))
    gzips.append(subprocess.Popen(["gzip", "-f", path]))
    for child in gzips:
        if child.wait() != 0:
            raise RuntimeError("gzip failed on an archive file")
    path = os.path.join(archive_dir, WELL_KNOWN_PATH)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(state.to_json())
    return {
        "anchor": anchor, "modified": modified, "bucket_list_hash": header.bucketListHash,
        "header_hash": header_hash, "levels": counts,
    }


# -- the workload ------------------------------------------------------------------------


class Workload(N.NodeWorkload):

    def __init__(self, ctx):
        from benchmarks.generators.replay import archive_of

        self.ctx = ctx
        p = self.p = ctx.traffic["params"]
        self.width = N.width_of(ctx.config, ctx.rehearsal)
        self.n = N.accounts_of(ctx.config, ctx.rehearsal)
        if self.width % 2 or self.n < 3 * (self.width // 2):
            raise SystemExit("state-ledgers: a set needs an even width and 1.5 x width distinct residents")
        self.parts: Dict[str, float] = {}
        t = time.time()
        self.pubs = public_keys(b"acct", ctx.seed, 0, self.n, ctx.root, os.path.join(ctx.work, "keys"))
        t = self._part("keys", t, "%d residents' public keys" % self.n)
        self.archive_dir = os.path.join(ctx.work, "archive")
        self.archive = write_archive(self.archive_dir, ctx.seed, self.pubs, self.width, p)
        self.anchor = self.archive["anchor"]
        t = self._part("archive", t, "bucket files by level %s, the state and the anchor header" % self.archive["levels"])

        cfg = N.make_config(ctx.config, ctx.work, ctx.rehearsal, ctx.traffic.get("node"))
        cfg.HISTORY = archive_of(cfg.HISTORY, self.archive_dir, False)
        self.node = N.Node(cfg, self.width)
        self.catch_up(self.node, float(p["catchup_deadline_s"]))
        self.at_anchor = {
            "lcl": self.node.lm.last_closed.hash,
            "bucket_list_hash": self.node.app.bucket_manager.get_hash(),
            "history": self._history(),
        }
        self.node.fee = self.node.lm.get_tx_fee()
        t = self._part("catch-up", t, "mode minimal to ledger %d; history %s" % (self.anchor, self.at_anchor["history"]))
        self.plain_dir = os.path.join(ctx.work, "plain")
        self._copy_node(self.plain_dir)
        t = self._part("copy", t, "the node's database and buckets for the plain node")

        self.amount, self.create_balance = int(p["amount"]), int(p["create_balance"])
        self.round = 0
        self.drawn: set = set()  # residents any built set touches
        self._next_seq: Dict[int, int] = {}
        self.new_keys = 0  # created accounts so far: key ``i`` of label "new"
        self._new_pubs = np.empty((0, 32), np.uint8)
        self.built_in_window = 0
        self.offered = 0
        self._sets: list = []
        prebuilt = int(
            p["rehearsal_sets"] if ctx.rehearsal
            else math.ceil(ctx.seconds / (p["floor_close_s_per_tx"] * self.width)) + 6
        )
        self._more_new_keys(prebuilt * (self.width // 2))
        for _ in range(prebuilt):
            self._sets.append(self._build())
        self._part("sets", t, "%d sets of %d built and signed" % (prebuilt, self.width))

    def _part(self, name: str, since: float, what: str) -> float:
        now = time.time()
        self.parts[name] = now - since
        print("set-up: %.1f s %s: %s" % (now - since, name, what), flush=True)
        return now

    # -- set-up: the catch-up ----------------------------------------------------------
    @staticmethod
    def catch_up(node, deadline_s: float) -> None:
        """``/catchup?mode=minimal`` and the clock cranked until the node is
        synced on the archive's anchor; raises past the deadline or when the
        catch-up fails."""
        from stellar_tpu.ledger.manager import LedgerState

        node.app.start()
        node.lm.start_catchup(mode="minimal")
        deadline = time.monotonic() + deadline_s
        while node.lm.state == LedgerState.LM_CATCHING_UP_STATE:
            if time.monotonic() > deadline:
                raise RuntimeError("the catch-up passed its deadline of %.0f s" % deadline_s)
            node.clock.crank(block=True, max_block=0.05)
        if node.lm.state != LedgerState.LM_SYNCED_STATE:
            raise RuntimeError("the catch-up failed (state %s)" % node.lm.state)

    def _history(self) -> dict:
        return {k: v for k, v in self.node.app.history_manager.stats().items() if isinstance(v, (int, float))}

    def _copy_node(self, to: str) -> None:
        os.makedirs(to)
        src = sqlite3.connect(f"file:{self.db_path()}?mode=ro", uri=True)
        dst = sqlite3.connect(os.path.join(to, "node.db"))
        try:
            src.backup(dst)
        finally:
            src.close()
            dst.close()
        shutil.copytree(self.node.cfg.BUCKET_DIR_PATH, os.path.join(to, "buckets"))

    # -- set-up: the sets ------------------------------------------------------------------
    def _more_new_keys(self, count: int) -> None:
        have = len(self._new_pubs)
        fresh = public_keys(b"new", self.ctx.seed, have, have + count, self.ctx.root, os.path.join(self.ctx.work, "keys"))
        self._new_pubs = np.concatenate([self._new_pubs, fresh])

    def _secret(self, resident: int):
        from stellar_tpu.crypto.keys import SecretKey

        return SecretKey.from_seed(key_seed(b"acct", self.ctx.seed, resident))

    def _build(self) -> list:
        """One set: ``width`` / 2 CREATE_ACCOUNT and as many native PAYMENT,
        the residents drawn without replacement from all of them."""
        import stellar_tpu.xdr as X

        half = self.width // 2
        rng = np.random.default_rng([self.ctx.seed, 0x5E7, self.round])
        drawn = rng.choice(self.n, 3 * half, replace=False)
        self.drawn.update(drawn.tolist())
        if self.new_keys + half > len(self._new_pubs):
            self._more_new_keys(4 * half)
        ops = []
        for i in range(half):
            dest = X.PublicKey.from_ed25519(self._new_pubs[self.new_keys + i].tobytes())
            ops.append(X.Operation(None, X.OperationBody(
                X.OperationType.CREATE_ACCOUNT, X.CreateAccountOp(dest, self.create_balance))))
        self.new_keys += half
        for d in drawn[2 * half :]:
            dest = X.PublicKey.from_ed25519(self.pubs[d].tobytes())
            ops.append(X.Operation(None, X.OperationBody(
                X.OperationType.PAYMENT, X.PaymentOp(dest, X.Asset.native(), self.amount))))
        txs = []
        for s, op in zip(drawn[: 2 * half].tolist(), ops):
            seq = self._next_seq.get(s, int(self.archive["modified"][s]) << 32) + 1
            self._next_seq[s] = seq
            txs.append(N.tx_frame(self.node.app.network_id, self.node.fee, self._secret(s), seq, [op]).envelope.to_xdr())
        order = rng.permutation(len(txs))
        self.round += 1
        return [txs[i] for i in order]

    def step(self, in_window: bool) -> Reading:
        if not self._sets:
            self._sets.append(self._build())
            if in_window:
                self.built_in_window += 1
        node = self.node
        txs = node.frames(self._sets.pop(0))
        ledger_data = node.ledger_data(txs)
        t0 = time.monotonic()
        if not ledger_data.tx_set.check_valid(node.app):
            raise RuntimeError("a set did not validate")
        node.lm.externalize_value(ledger_data)
        t1 = time.monotonic()
        self.offered += len(txs)
        del ledger_data, txs
        node.settle()
        return Reading(t0, t1, self.width)

    # -- what the harness reads ----------------------------------------------------------------
    def counters(self) -> dict:
        """The node's, with the two blocks of ``/info`` this cell's readers
        read — ``entry_cache`` and ``history`` — where the program has them."""
        from stellar_tpu.ledger.entryframe import entry_cache_of

        out = self.node.counters()
        stats = getattr(entry_cache_of(self.node.app.database), "stats", None)
        if stats is not None:
            out["entry_cache"] = stats()
        out["history"] = self._history()
        return out

    def notes(self) -> dict:
        return {
            "built_in_window": self.built_in_window, "sets_left": len(self._sets), "set_up_parts": self.parts,
            "residents_by_level": self.archive["levels"], "anchor": self.anchor, "accounts_created": self.new_keys,
        }

    def check(self, check) -> tuple:
        """``NodeWorkload.check``'s rows — the plain node started from the
        copy taken after the catch-up, the balances' arithmetic the plain
        ledger's over all the residents — then the state's own rows
        (``benchmarks/reference_state.py``); every limit 0."""
        from benchmarks import reference as ref
        from benchmarks import reference_state as RS

        node = self.node
        node.settle()
        closed = node.closed
        inv = node.app.invariants.dump_info()
        check.compare("invariant_violations", int(inv.get("total_violations", 0)), 0)
        check.compare("closes_not_invariant_checked", max(0, len(closed) - int(inv.get("closes_checked", 0))), 0)
        lcl_seq, lcl_hash, closed_txs, then = self._at_close
        check.compare("durable_lcl_seq_behind", lcl_seq - (then["top"] or 0), 0, "as the last timed close returned")
        check.compare("durable_lcl_hash_differs", 0 if then["lcl"] == lcl_hash else 1, 0, f"lcl {lcl_seq}")
        check.compare(
            "closed_txs_not_yet_in_txhistory", max(0, closed_txs - then["txhistory"]), 0,
            f"{then['txhistory']} rows as the last timed close returned",
        )
        durable = ref.durable_state(self.db_path(), balances=False)
        passphrase = node.cfg.NETWORK_PASSPHRASE
        node.stop()
        missing = max(0, self.offered - durable["txhistory"])
        check.compare("txs_not_in_txhistory", missing, 0, f"{durable['txhistory']} rows after the drain")

        want = plain_node_hashes(closed, self.ctx.config, passphrase, self.plain_dir, self.width)
        bad = sum(1 for c, h in zip(closed, want) if c.hash != h) + max(0, len(closed) - len(want))
        check.compare("ledger_hashes_differing", bad, 0, f"of {len(closed)} closes, against a cpu node on the copied state")

        # the archive's files and the database file, by the plain reader
        state = RS.read_archive(self.archive_dir, self.anchor)
        check.compare(
            "anchor_bucket_list_hash_differs",
            (state["bucket_list_hash"] != state["header"]["bucket_list_hash"])
            + (state["bucket_list_hash"] != self.at_anchor["bucket_list_hash"])
            + (state["header"]["hash"] != self.at_anchor["lcl"]), 0,
            "plain reader's against the anchor header's and the node's at the anchor",
        )
        check.compare("archive_buckets_off", state["buckets_off"], 0, f"of {state['buckets']} files: hash, order, entry shape")
        found = RS.compare(
            state, [(c.seq, c.envelopes) for c in closed], self.db_path(), passphrase,
            sample=int(self.p["sample"]), seed=self.ctx.seed,
        )
        for row in RS.ROWS:
            check.compare(row, found[row], 0, found["detail"].get(row, ""))
        self._found = found["notes"]
        return self.offered, missing + bad + found["result_codes_differing"] + found["verdicts_differing"]


def plain_node_hashes(closed: list, cfg_file: dict, passphrase: str, plain_dir: str, width: int) -> List[bytes]:
    """Ledger hashes of a plain ``SIGNATURE_BACKEND="cpu"`` node — as
    ``reference.replay_hashes`` configures it, but started on the copy of the
    node's database and buckets taken after the catch-up — fed ``closed``."""
    import stellar_tpu.xdr as X
    from stellar_tpu.crypto.keys import PubKeyUtils, SecretKey
    from stellar_tpu.herder.ledgerclose import LedgerCloseData
    from stellar_tpu.herder.txset import TxSetFrame
    from stellar_tpu.main.application import Application
    from stellar_tpu.main.config import Config
    from stellar_tpu.tx.frame import TransactionFrame
    from stellar_tpu.util.clock import REAL_TIME, VirtualClock
    from stellar_tpu.xdr.scp import SCPQuorumSet

    PubKeyUtils.clear_verify_sig_cache()
    cfg = Config()
    cfg.NETWORK_PASSPHRASE = passphrase
    cfg.DATABASE = "sqlite3://" + os.path.join(plain_dir, "node.db")
    cfg.BUCKET_DIR_PATH = os.path.join(plain_dir, "buckets")
    cfg.TMP_DIR_PATH = os.path.join(plain_dir, "tmp")
    cfg.RUN_STANDALONE = True
    cfg.MANUAL_CLOSE = True
    cfg.NODE_IS_VALIDATOR = True
    cfg.HTTP_PORT = 0
    cfg.SIGNATURE_BACKEND = "cpu"
    cfg.CLOSE_PIPELINE = False
    cfg.INGEST_BATCH = False
    cfg.INVARIANT_CHECKS = []
    cfg.BACKGROUND_BUCKET_MERGE = False
    cfg.DESIRED_MAX_TX_PER_LEDGER = width
    cfg.NODE_SEED = SecretKey.from_seed(hashlib.sha256(b"bench reference node").digest())
    cfg.QUORUM_SET = SCPQuorumSet(1, [cfg.NODE_SEED.get_public_key()], [])
    clock = VirtualClock(REAL_TIME)
    app = Application.create(clock, cfg, new_db=False)
    hashes = []
    try:
        app.start()
        lm = app.ledger_manager
        for rec in closed:
            txs = [TransactionFrame(app.network_id, X.TransactionEnvelope.from_xdr(b)) for b in rec.envelopes]
            txset = TxSetFrame(lm.last_closed.hash, txs)
            txset.sort_for_hash()
            lm.close_ledger(LedgerCloseData(rec.seq, txset, rec.value))
            hashes.append(lm.last_closed.hash)
    except Exception as e:  # a reference that cannot follow has disagreed
        print(f"reference: the plain node stopped at close {len(hashes) + 1}: {e!r}", flush=True)
    finally:
        app.graceful_stop()
        clock.shutdown()
    return hashes


if __name__ == "__main__":
    # a key-derivation child: keys <label> <seed> <lo> <hi> <out>
    _, what, label, seed, lo, hi, out = sys.argv
    if what != "keys":
        raise SystemExit("state_closes: unknown child task %r" % what)
    with open(out, "wb") as f:
        f.write(derive_public_keys(label.encode(), int(seed), int(lo), int(hi)))
