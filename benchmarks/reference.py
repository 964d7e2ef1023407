"""The plain references, and the comparisons that decide ``correct``.

* Signatures: libsodium's ``crypto_sign_verify_detached``, bound here
  through ctypes and not through the program's binding.
* Ledgers: the same transaction sets, in the same order with the same close
  values, replayed through the program's plainest path — a
  ``SIGNATURE_BACKEND="cpu"`` node with the close pipeline, parallel apply,
  ingest batching and the invariant plane off, on in-memory sqlite — and
  every ledger hash compared.  (The issue fixes this as the reference; it
  shares the apply code with the node under test, so the account balances
  are also checked by plain arithmetic, which shares nothing.)
* Durability: the moment the last timed close has returned, and before the
  node is stopped or flushed in any way, its database file is opened
  read-only with ``sqlite3`` alone and must hold that ledger's hash and one
  ``txhistory`` row per transaction closed.

All of it runs after the window has closed and is not counted in set-up.
Every number compared is printed beside its limit; every limit is 0 (the
comparisons are exact).
"""

from __future__ import annotations

import ctypes
import ctypes.util
import sqlite3
from typing import Dict, List, Sequence, Tuple


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


def sodium_verdicts(items: Sequence[Tuple[bytes, bytes, bytes]]) -> List[bool]:
    """libsodium's verdict on each (public key, message, signature)."""
    lib = _sodium()
    fn = lib.crypto_sign_verify_detached
    fn.argtypes = [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_ulonglong, ctypes.c_char_p]
    fn.restype = ctypes.c_int
    out = []
    for pk, msg, sig in items:
        if len(pk) != 32 or len(sig) != 64:
            out.append(False)
            continue
        out.append(fn(sig, msg, len(msg), pk) == 0)
    return out


class Check:
    """Numbers compared, each beside its limit; ``ok`` when none is over."""

    def __init__(self):
        self.rows: List[dict] = []

    def compare(self, name: str, value, limit, detail: str = "") -> bool:
        ok = value <= limit
        self.rows.append({"name": name, "value": value, "limit": limit, "ok": ok, "detail": detail})
        return ok

    @property
    def ok(self) -> bool:
        return all(r["ok"] for r in self.rows)

    def print(self) -> None:
        for r in self.rows:
            print(
                "check %-28s value %-12s limit %-6s %s %s"
                % (r["name"], r["value"], r["limit"], "ok" if r["ok"] else "FAILED", r["detail"]),
                flush=True,
            )


# -- ledgers ----------------------------------------------------------------


def replay_hashes(closed: list, cfg_file: dict, passphrase: str, work: str) -> List[bytes]:
    """Ledger hashes of a plain cpu node fed ``closed`` (``node.Closed``
    records) from genesis."""
    from stellar_tpu.crypto.keys import PubKeyUtils
    from stellar_tpu.herder.ledgerclose import LedgerCloseData
    from stellar_tpu.herder.txset import TxSetFrame
    from stellar_tpu.main.application import Application
    from stellar_tpu.main.config import Config
    from stellar_tpu.tx.frame import TransactionFrame
    from stellar_tpu.util.clock import REAL_TIME, VirtualClock
    from stellar_tpu.crypto.keys import SecretKey
    from stellar_tpu.xdr.scp import SCPQuorumSet
    import hashlib
    import os

    # the verify cache is process-wide: what the node under test latched
    # must not answer for the reference
    PubKeyUtils.clear_verify_sig_cache()
    cfg = Config()
    cfg.NETWORK_PASSPHRASE = passphrase
    cfg.DATABASE = "sqlite3://:memory:"
    cfg.BUCKET_DIR_PATH = os.path.join(work, "ref-buckets")
    cfg.TMP_DIR_PATH = os.path.join(work, "ref-tmp")
    cfg.RUN_STANDALONE = True
    cfg.MANUAL_CLOSE = True
    cfg.NODE_IS_VALIDATOR = True
    cfg.HTTP_PORT = 0
    cfg.SIGNATURE_BACKEND = "cpu"
    cfg.CLOSE_PIPELINE = False
    cfg.PARALLEL_APPLY = False
    cfg.INGEST_BATCH = False
    cfg.INVARIANT_CHECKS = []
    cfg.BACKGROUND_BUCKET_MERGE = False
    cfg.DESIRED_MAX_TX_PER_LEDGER = cfg_file["node"]["DESIRED_MAX_TX_PER_LEDGER"]
    cfg.NODE_SEED = SecretKey.from_seed(hashlib.sha256(b"bench reference node").digest())
    cfg.QUORUM_SET = SCPQuorumSet(1, [cfg.NODE_SEED.get_public_key()], [])
    clock = VirtualClock(REAL_TIME)
    app = Application.create(clock, cfg, new_db=True)
    hashes = []
    try:
        lm = app.ledger_manager
        for rec in closed:
            txs = [TransactionFrame(app.network_id, env) for env in rec.envelopes]
            txset = TxSetFrame(lm.last_closed.hash, txs)
            txset.sort_for_hash()
            lm.close_ledger(LedgerCloseData(rec.seq, txset, rec.value))
            hashes.append(lm.last_closed.hash)
    except Exception as e:  # a reference that cannot follow has disagreed
        print(f"reference: replay stopped at ledger {len(hashes) + 1}: {e!r}", flush=True)
    finally:
        app.graceful_stop()
        clock.shutdown()
    return hashes


def durable_state(db_path: str, balances: bool = True) -> dict:
    """What a fresh read-only reader finds in the node's database file."""
    con = sqlite3.connect(f"file:{db_path}?mode=ro", uri=True)
    try:
        lcl = con.execute(
            "SELECT state FROM storestate WHERE statename = 'lastclosedledger'"
        ).fetchone()
        rows = con.execute("SELECT COUNT(*) FROM txhistory").fetchone()[0]
        top = con.execute("SELECT MAX(ledgerseq) FROM ledgerheaders").fetchone()[0]
        found = dict(con.execute("SELECT accountid, balance FROM accounts").fetchall()) if balances else {}
    finally:
        con.close()
    return {"lcl": lcl[0] if lcl else None, "txhistory": rows, "top": top, "balances": found}


def expected_balances(closed: list, genesis: Dict[str, int], fee: int, strkey_of) -> Dict[str, int]:
    """Account balances by plain arithmetic over the closed envelopes:
    native payments and account creations only, every one successful."""
    bal = dict(genesis)
    for rec in closed:
        for env in rec.envelopes:
            tx = env.tx
            src = strkey_of(tx.sourceAccount)
            bal[src] = bal.get(src, 0) - tx.fee
            for op in tx.operations:
                body = op.body.value
                dest = strkey_of(body.destination)
                amount = getattr(body, "amount", None)
                if amount is None:
                    amount = body.startingBalance
                bal[src] -= amount
                bal[dest] = bal.get(dest, 0) + amount
    return bal
