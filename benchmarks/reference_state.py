"""The plain reference of ``state1m``: a node's state, from an archive's files.

What a node that caught up in mode minimal must hold, and what it must hold
after closing the recorded sets on top — from the files of the history archive
and from nothing else: the ``HistoryArchiveState``, its bucket files and the
anchor checkpoint's ledger file are walked with ``json`` / ``gzip`` / ``struct``
/ ``hashlib`` (a bucket's hash is SHA-256 over the SHA-256 of each record as
written, a level's SHA-256(curr ‖ snap), the list's SHA-256 over the levels'),
the buckets are laid over each other oldest first into the starting plain
``Ledger`` (``benchmarks/reference_mixed.py``), the closed sets are applied by
its plain arithmetic in the order the node stored, verdicts are libsodium's,
and the node's side is its database file read by ``sqlite3`` alone.  Nothing is
imported from ``stellar_tpu`` or from ``tests/``.
"""

from __future__ import annotations

import base64
import ctypes
import gzip
import hashlib
import json
import os
import random
import sqlite3
import struct
from typing import Dict, List, Sequence, Tuple

from benchmarks import reference_mixed as RM
from benchmarks import reference_replay as RR

ZERO = bytes(32)
# a BucketEntry of an account with no signers, no home domain, no inflation
# destination: kind, lastModified, entry type, key type, key, balance, seqNum,
# numSubEntries, inflationDest?, flags, len(homeDomain), thresholds,
# len(signers), the two exts
PLAIN_ACCOUNT = struct.Struct(">iIii32sqQIIII4sIii")

# the rows ``compare`` gives, each a count that has to be 0
ROWS = (
    "touched_accounts_off", "created_accounts_off", "untouched_sample_off", "account_rows_off",
    "balance_sum_off", "fee_pool_off", "result_codes_differing", "verdicts_differing",
)


def bucket_path(archive_dir: str, h: bytes) -> str:
    x = h.hex()
    return os.path.join(archive_dir, "bucket", x[0:2], x[2:4], x[4:6], f"bucket-{x}.xdr.gz")


def _account(body: bytes) -> Tuple[bytes, tuple]:
    """A live account entry's (key, (balance, sequence number, signers,
    sub-entries)), whatever optional parts it carries."""
    c = RR._Cursor(body, 4)
    c.u32()  # lastModifiedLedgerSeq
    if c.i32() != 0:
        raise ValueError("a bucket entry that is not an account: not one this state holds")
    key, balance, seq, subs = c.key(), c.i64(), c.u64(), c.u32()
    if c.u32():
        c.key()
    c.u32()  # flags
    c.opaque()  # home domain
    c.take(4)  # thresholds
    signers = c.u32()
    for _ in range(signers):
        c.key()
        c.u32()
    c.i32()
    c.i32()
    if c.at != len(body):
        raise ValueError("an account entry with bytes left over")
    return key, (balance, seq, signers, subs)


def read_bucket(path: str) -> Tuple[bytes, List[tuple], bool]:
    """-> (the bucket's hash, [(key, value-or-None)] in the file's order —
    None for a dead key —, whether the keys ascend as a bucket's must)."""
    with gzip.open(path, "rb") as f:
        data = f.read()
    outer, sha = hashlib.sha256(), hashlib.sha256
    out, at, ordered, last = [], 0, True, b""
    while at < len(data):
        (mark,) = struct.unpack_from(">I", data, at)
        n = mark & 0x7FFFFFFF
        if not mark & 0x80000000 or at + 4 + n > len(data):
            raise ValueError(f"{path}: a record mark that is not one")
        outer.update(sha(data[at : at + 4 + n]).digest())
        body = data[at + 4 : at + 4 + n]
        at += 4 + n
        if n == PLAIN_ACCOUNT.size:
            kind, _mod, etype, ktype, key, balance, seq, subs, infl, _flags, dom, _thr, signers, _a, _e = \
                PLAIN_ACCOUNT.unpack(body)
            if (kind, etype, ktype, infl, dom) == (0, 0, 0, 0, 0):
                value = (balance, seq, signers, subs)
            else:
                key, value = _account(body)
        elif struct.unpack_from(">i", body)[0] == 1:  # a dead key
            c = RR._Cursor(body, 4)
            if c.i32() != 0:
                raise ValueError("a dead key that is not an account's")
            key, value = c.key(), None
        else:
            key, value = _account(body)
        ordered = ordered and last < key
        last = key
        out.append((key, value))
    return outer.digest(), out, ordered


def read_archive(archive_dir: str, anchor: int) -> dict:
    """The state an archive holds at ``anchor``.  -> ``accounts`` {raw key:
    (balance, sequence number)}, ``bucket_list_hash`` recomputed from the
    bucket files, ``header`` (the anchor's, ``reference_replay.header_entry``),
    ``buckets`` read and ``buckets_off``: files whose records do not hash to
    their name, whose keys do not ascend, or — what the plain ledger cannot
    carry — accounts with signers or sub-entries, pending merges in the
    state, a state or header of another ledger."""
    with open(os.path.join(archive_dir, ".well-known", "stellar-history.json")) as f:
        has = json.load(f)
    off = 0 if has["currentLedger"] == anchor else 1
    accounts: Dict[bytes, tuple] = {}
    level_hashes, levels, buckets = [], [], 0
    for level in has["currentBuckets"]:
        off += 0 if level.get("next", {}).get("state", 0) == 0 else 1
        hashes, layers = {}, []
        for name in ("snap", "curr"):
            h = bytes.fromhex(level[name])
            if h != ZERO:
                got, entries, ordered = read_bucket(bucket_path(archive_dir, h))
                buckets += 1
                off += (got != h) + (not ordered)
                layers.append(entries)
                h = got
            hashes[name] = h
        level_hashes.append(hashlib.sha256(hashes["curr"] + hashes["snap"]).digest())
        levels.append(layers)
    # oldest first: the deepest level's snap, then its curr, up to level 0
    for layers in reversed(levels):
        for entries in layers:
            for key, value in entries:
                if value is None:
                    accounts.pop(key, None)
                else:
                    off += (value[2] != 0) + (value[3] != 0)
                    accounts[key] = value[:2]
    header = next(
        (h for h in map(RR.header_entry, RR.records(RR.archive_file(archive_dir, "ledger", anchor))) if h["seq"] == anchor),
        None,
    )
    if header is None:
        raise ValueError("the archive's ledger file lacks the anchor's header")
    off += header["hash"] != header["claimed_hash"]
    return {
        "accounts": accounts, "bucket_list_hash": hashlib.sha256(b"".join(level_hashes)).digest(),
        "header": header, "buckets": buckets, "buckets_off": off,
    }


def stored_header(db_path: str) -> dict:
    """The newest header of a database file, as ``header_entry`` reads one."""
    con = sqlite3.connect(f"file:{db_path}?mode=ro", uri=True)
    try:
        (data,) = con.execute("SELECT data FROM ledgerheaders ORDER BY ledgerseq DESC LIMIT 1").fetchone()
    finally:
        con.close()
    return RR.header_entry(ZERO + base64.b64decode(data))


def compare(state: dict, closed: Sequence[Tuple[int, List[bytes]]], db_path: str, passphrase: str,
            sample: int, seed: int) -> dict:
    """Close ``closed`` — (ledger, the set's envelopes as XDR bytes) — on the
    plain ledger that ``state`` starts, in the order the database file's
    ``txhistory`` gives, and hold the file's accounts to it.  -> the counts
    named in ``ROWS`` (each has to be 0), ``detail`` and ``notes``."""
    network_id = hashlib.sha256(passphrase.encode()).digest()
    lib = RR._sodium()
    verify = lib.crypto_sign_verify_detached
    verify.argtypes = [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_ulonglong, ctypes.c_char_p]
    header = state["header"]
    ledger = RM.Ledger(state["accounts"], header["base_fee"], header["base_reserve"], fee_pool=header["fee_pool"])
    history = RM.stored_history(db_path)
    sets, touched, created = [], set(), set()
    verdicts_differing = signatures = 0
    for seq, envelopes in closed:
        by_id = {}
        codes = {txid: code for txid, (code, _ops) in history.get(seq, [])}
        for blob in envelopes:
            e = RR.envelope(RR._Cursor(blob), network_id)
            signed_by = []
            for hint, sig in e["signatures"]:
                signatures += 1
                ok = len(sig) == 64 and hint == e["source"][-4:] and verify(sig, e["hash"], 32, e["source"]) == 0
                signed_by.append(e["source"] if ok else b"")
            # the node's verdict on a one-signature transaction is its stored code
            node_ok = codes.get(e["hash"].hex()) != "txBAD_AUTH"
            verdicts_differing += node_ok != all(signed_by)
            by_id[e["hash"].hex()] = RM.Tx(e["source"], e["seq"], e["fee"], e["ops"], tuple(signed_by))
            touched.add(e["source"])
            for op in e["ops"]:
                (created if op[0] == "create" else touched).add(op[1])
        sets.append((seq, by_id))
    found = RM.replay(ledger, sets, history)
    have = RR.stored_accounts(db_path)
    want = ledger.accounts
    touched_off = sum(1 for k in touched if have.get(k) != tuple(want.get(k) or ()))
    created_off = sum(1 for k in created if have.get(k) != tuple(want.get(k) or ()))
    untouched = [k for k in state["accounts"] if k not in touched and k not in created]
    picked = random.Random(seed).sample(untouched, min(sample, len(untouched)))
    sample_off = sum(1 for k in picked if have.get(k) != tuple(state["accounts"][k]))
    now = stored_header(db_path)
    coins = sum(balance for balance, _seq in have.values()) + now["fee_pool"]
    out = {
        "touched_accounts_off": touched_off, "created_accounts_off": created_off,
        "untouched_sample_off": sample_off, "account_rows_off": abs(len(have) - len(want)),
        "balance_sum_off": abs(coins - header["total_coins"]) + abs(now["total_coins"] - header["total_coins"]),
        "fee_pool_off": abs(now["fee_pool"] - ledger.fee_pool),
        "result_codes_differing": found["codes_differing"], "verdicts_differing": verdicts_differing,
    }
    out["detail"] = {
        "touched_accounts_off": f"of {len(touched)} residents a closed transaction touched: balance, sequence number, existence",
        "created_accounts_off": f"of {len(created)} accounts a closed transaction created",
        "untouched_sample_off": f"of a seeded sample of {len(picked)} among {len(untouched)} residents nothing touched",
        "account_rows_off": f"{len(have)} rows against the plain ledger's {len(want)} accounts",
        "balance_sum_off": f"balances + fee pool {coins} against the anchor's totalCoins {header['total_coins']}",
        "result_codes_differing": f"of {found['txs']} closed transactions; {found['failed_at_apply']} failed at apply "
                                  f"on the plain ledger; {found['orders_refused']} stored orders refused",
        "verdicts_differing": f"of {signatures} signatures against libsodium",
    }
    out["notes"] = {"failed_at_apply": found["failed_at_apply"], "accounts_at_end": len(want), "signatures": signatures}
    return out
