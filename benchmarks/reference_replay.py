"""The plain reference of ``catchup64``: a history archive replayed plainly.

What a node that catches up in mode complete must arrive at, computed from
the files a publisher left in the archive and from nothing else: the header
chain, every signature's verdict, and the accounts and the fee pool by plain
arithmetic.  The arithmetic is the benchmark's plain ledger
(``benchmarks/reference_mixed.py``, the copy of ``tests/reference_apply.py``);
everything below the mark is the section of the same name in
``tests/reference_apply.py``, letter for letter (tier-1 holds the two equal).
Nothing is imported from ``stellar_tpu`` or from ``tests/``.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from benchmarks.reference_mixed import Ledger, Tx


# -- a history archive, replayed plainly ----------------------------------------
#
# What a node's catch-up (CATCHUP_COMPLETE) must arrive at, from the files a
# publisher left in a history archive and nothing else.  Nothing here comes
# from ``stellar_tpu``: the record-marked XDR streams are walked with
# ``struct``, hashes are ``hashlib``'s, verdicts libsodium's through
# ``ctypes``, and the apply is the plain ledger above.  Operations other than
# CREATE_ACCOUNT and the native PAYMENT are refused: the deployments that
# publish these archives make no others.
#
# ``tests/reference_apply.py`` and ``benchmarks/reference_replay.py`` hold this
# section letter for letter (tier-1 compares them).

import base64
import ctypes
import ctypes.util
import gzip
import hashlib
import os
import struct

ENVELOPE_TYPE_TX = 2
TX_RESULT_CODES = {
    0: "txSUCCESS", -1: "txFAILED", -2: "txTOO_EARLY", -3: "txTOO_LATE", -4: "txMISSING_OPERATION",
    -5: "txBAD_SEQ", -6: "txBAD_AUTH", -7: "txINSUFFICIENT_BALANCE", -8: "txNO_ACCOUNT",
    -9: "txINSUFFICIENT_FEE", -10: "txBAD_AUTH_EXTRA", -11: "txINTERNAL_ERROR",
}


class _Cursor:
    """A reading position in XDR bytes."""

    def __init__(self, data: bytes, at: int = 0):
        self.data, self.at = data, at

    def take(self, n: int) -> bytes:
        if self.at + n > len(self.data):
            raise ValueError("XDR runs past the end of its record")
        out = self.data[self.at : self.at + n]
        self.at += n
        return out

    def u32(self) -> int:
        return struct.unpack(">I", self.take(4))[0]

    def i32(self) -> int:
        return struct.unpack(">i", self.take(4))[0]

    def u64(self) -> int:
        return struct.unpack(">Q", self.take(8))[0]

    def i64(self) -> int:
        return struct.unpack(">q", self.take(8))[0]

    def opaque(self) -> bytes:
        n = self.u32()
        out = self.take(n)
        self.take(-n % 4)
        return out

    def key(self) -> bytes:
        if self.i32() != 0:
            raise ValueError("a public key that is not ed25519")
        return self.take(32)


def archive_file(archive_dir: str, category: str, checkpoint: int) -> str:
    """Where an archive keeps a checkpoint's file of ``category``
    (``ledger``, ``transactions``, ``results``)."""
    h = "%08x" % checkpoint
    return os.path.join(archive_dir, category, h[0:2], h[2:4], h[4:6], f"{category}-{h}.xdr.gz")


def records(path: str) -> List[bytes]:
    """The bodies of a gzipped record-marked XDR file (RFC 5531: four bytes
    of length with the high bit set, then the body)."""
    with gzip.open(path, "rb") as f:
        data = f.read()
    out, at = [], 0
    while at < len(data):
        (mark,) = struct.unpack_from(">I", data, at)
        n = mark & 0x7FFFFFFF
        if at + 4 + n > len(data):
            raise ValueError(f"{path}: truncated record")
        out.append(data[at + 4 : at + 4 + n])
        at += 4 + n
    return out


def header_entry(body: bytes) -> dict:
    """A LedgerHeaderHistoryEntry: the hash the archive claims, and the
    header's fields with the bytes they were read from."""
    c = _Cursor(body)
    claimed = c.take(32)
    start = c.at
    h = {"claimed_hash": claimed, "version": c.u32(), "previous": c.take(32)}
    h["tx_set_hash"], h["close_time"] = c.take(32), c.u64()
    h["upgrades"] = [c.opaque() for _ in range(c.u32())]
    c.i32()  # the value's ext
    h["tx_result_hash"], h["bucket_list_hash"] = c.take(32), c.take(32)
    h["seq"], h["total_coins"], h["fee_pool"] = c.u32(), c.i64(), c.i64()
    h["inflation_seq"], h["id_pool"] = c.u32(), c.u64()
    h["base_fee"], h["base_reserve"], h["max_tx_set_size"] = c.u32(), c.u32(), c.u32()
    c.take(4 * 32)  # the skip list
    c.i32()  # the header's ext
    h["hash"] = hashlib.sha256(body[start : c.at]).digest()
    return h


def _operation(c: _Cursor) -> tuple:
    if c.u32():
        raise ValueError("an operation with a source of its own")
    kind = c.i32()
    if kind == 0:
        return ("create", c.key(), c.i64())
    if kind == 1:
        dest = c.key()
        if c.i32() != 0:
            raise ValueError("a payment that is not native")
        return ("pay", dest, c.i64())
    raise ValueError(f"operation type {kind}: not one this replay applies")


def envelope(c: _Cursor, network_id: bytes) -> dict:
    """A TransactionEnvelope at the cursor: the transaction as the plain
    ledger takes it, its hash as the network signs it, the envelope's bytes
    and its signatures."""
    start = c.at
    source, fee, seq = c.key(), c.u32(), c.u64()
    if c.u32():
        c.take(16)  # time bounds: the replay applies none
    memo = c.i32()
    if memo == 1:
        c.opaque()
    elif memo == 2:
        c.take(8)
    elif memo in (3, 4):
        c.take(32)
    ops = tuple(_operation(c) for _ in range(c.u32()))
    c.i32()  # the transaction's ext
    tx_bytes = c.data[start : c.at]
    signatures = [(c.take(4), c.opaque()) for _ in range(c.u32())]
    contents = hashlib.sha256(network_id + struct.pack(">i", ENVELOPE_TYPE_TX) + tx_bytes).digest()
    return {
        "source": source, "fee": fee, "seq": seq, "ops": ops, "hash": contents,
        "signatures": signatures, "bytes": c.data[start : c.at],
    }


def tx_entry(body: bytes, network_id: bytes) -> Tuple[int, bytes, List[dict]]:
    """A TransactionHistoryEntry -> (ledger, the set's previous ledger hash,
    its envelopes in the file's order)."""
    c = _Cursor(body)
    seq, previous = c.u32(), c.take(32)
    return seq, previous, [envelope(c, network_id) for _ in range(c.u32())]


def result_entry(body: bytes) -> Tuple[int, List[Tuple[bytes, int, str]]]:
    """A TransactionHistoryResultEntry -> (ledger, [(transaction hash, fee
    charged, code)] in the order the publisher applied them).  An
    operation's result is walked over: every one this replay applies is an
    ``opINNER`` of a type and a code with nothing after."""
    c = _Cursor(body)
    seq, out = c.u32(), []
    for _ in range(c.u32()):
        tx_hash, fee, code = c.take(32), c.i64(), c.i32()
        if code in (0, -1):
            for _ in range(c.u32()):
                if c.i32() == 0:
                    c.take(8)
        c.i32()  # the result's ext
        out.append((tx_hash, fee, TX_RESULT_CODES[code]))
    return seq, out


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


def root_key(network_id: bytes) -> bytes:
    """The genesis account's public key: the network id is its seed."""
    pk, sk = ctypes.create_string_buffer(32), ctypes.create_string_buffer(64)
    _sodium().crypto_sign_seed_keypair(pk, sk, network_id)
    return pk.raw


def replay_archive(archive_dir: str, checkpoint: int, passphrase: str) -> dict:
    """Replay the checkpoint ``[1, checkpoint]`` of a fresh network from its
    archive files.  -> what a caught-up node has to show:

    ``hashes`` {ledger: header hash}, ``bucket_list_hash`` and ``fee_pool`` of
    the anchor, ``accounts`` {raw key: (balance, sequence number)}, ``txs``
    and ``signatures`` replayed — and the faults found on the way, each a
    count that has to be 0 in an honest archive: ``headers_off`` (a header
    whose bytes do not hash to the claimed hash, or that does not name the
    header before it), ``sets_off`` (a set that is not on its ledger's
    previous hash or does not hash to the header's ``txSetHash``),
    ``signatures_bad`` (libsodium's verdict), ``results_off`` (a
    transaction whose code or fee in the results file is not the plain
    ledger's, a results order that is no order of the set) and
    ``fee_pools_off`` (a header whose fee pool is not the plain ledger's)."""
    network_id = hashlib.sha256(passphrase.encode()).digest()
    headers = {h["seq"]: h for h in map(header_entry, records(archive_file(archive_dir, "ledger", checkpoint)))}
    sets = {
        seq: (previous, envs)
        for seq, previous, envs in (
            tx_entry(b, network_id) for b in records(archive_file(archive_dir, "transactions", checkpoint))
        )
    }
    results = dict(map(result_entry, records(archive_file(archive_dir, "results", checkpoint))))
    out = {"headers_off": 0, "sets_off": 0, "signatures_bad": 0, "results_off": 0, "fee_pools_off": 0}
    for seq in sorted(headers):
        h = headers[seq]
        before = headers.get(seq - 1)
        if h["hash"] != h["claimed_hash"] or (before is not None and h["previous"] != before["hash"]):
            out["headers_off"] += 1
    if sorted(headers) != list(range(1, checkpoint + 1)):
        out["headers_off"] += 1

    lib = _sodium()
    verify = lib.crypto_sign_verify_detached
    verify.argtypes = [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_ulonglong, ctypes.c_char_p]
    genesis = headers[1]
    ledger = Ledger(
        {root_key(network_id): (genesis["total_coins"], 0)}, genesis["base_fee"], genesis["base_reserve"],
    )
    txs = signatures = 0
    for seq in range(2, checkpoint + 1):
        h = headers[seq]
        previous, envs = sets.get(seq, (h["previous"], []))
        contents = hashlib.sha256(
            previous + b"".join(e["bytes"] for e in sorted(envs, key=lambda e: hashlib.sha256(e["bytes"]).digest()))
        ).digest()
        if previous != h["previous"] or contents != h["tx_set_hash"]:
            out["sets_off"] += 1
        by_hash = {}
        for e in envs:
            signed_by = []
            for hint, sig in e["signatures"]:
                signatures += 1
                ok = len(sig) == 64 and hint == e["source"][-4:] and verify(sig, e["hash"], 32, e["source"]) == 0
                out["signatures_bad"] += 0 if ok else 1
                signed_by.append(e["source"] if ok else b"")
            by_hash[e["hash"]] = Tx(e["source"], e["seq"], e["fee"], e["ops"], tuple(signed_by))
        stored = results.get(seq, [])
        order = [by_hash.get(tx_hash) for tx_hash, _fee, _code in stored]
        if len(stored) != len(by_hash) or None in order or len({t for t, _f, _c in stored}) != len(stored):
            out["results_off"] += len(by_hash)
            order = list(by_hash.values())
            stored = []
        pool = ledger.fee_pool
        codes = ledger.close(seq, order)
        for (code, _ops), (_tx_hash, fee, have), tx in zip(codes, stored, order):
            if code != have or fee != tx.fee:
                out["results_off"] += 1
        txs += len(order)
        # a version or fee upgrade would change the arithmetic: none is applied here
        for up in h["upgrades"]:
            if struct.unpack(">i", up[:4])[0] != 3:
                raise ValueError("an upgrade that is not of maxTxSetSize")
        if ledger.fee_pool != h["fee_pool"] or ledger.fee_pool - pool != sum(t.fee for t in order):
            out["fee_pools_off"] += 1
    anchor = headers[checkpoint]
    out.update(
        hashes={seq: h["hash"] for seq, h in headers.items()},
        bucket_list_hash=anchor["bucket_list_hash"], fee_pool=ledger.fee_pool,
        accounts={k: tuple(v) for k, v in ledger.accounts.items()}, txs=txs, signatures=signatures,
    )
    return out


def stored_accounts(db_path: str) -> Dict[bytes, Tuple[int, int]]:
    """{raw key: (balance, sequence number)} as a database file holds them,
    read by ``sqlite3`` alone (an account id is a strkey: a version byte,
    the key and a checksum in base32)."""
    import sqlite3

    con = sqlite3.connect(f"file:{db_path}?mode=ro", uri=True)
    try:
        rows = con.execute("SELECT accountid, balance, seqnum FROM accounts").fetchall()
    finally:
        con.close()
    return {base64.b32decode(aid)[1:33]: (balance, seq) for aid, balance, seq in rows}
