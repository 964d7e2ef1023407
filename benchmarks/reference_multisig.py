"""The plain reference of the ``multisig5000`` configuration: who may spend
from an account held under weighted signers, by plain arithmetic.

It shares nothing with ``stellar_tpu/tx/frame.py``.  The signer table is the
one the generator built from the seed (never read back from the node), the
hash a signature signs is taken with ``hashlib`` over the transaction's XDR
bytes, every verdict is libsodium's through the ctypes binding of
``benchmarks/reference.py``, and what the node did is read from its database
file with ``sqlite3`` alone.

The rule is the source's (``TransactionFrame.cpp:129-167``): signatures are
taken in envelope order; a signature counts for the first signer of the
account, not yet counted, whose key ends in the signature's four-byte hint
and under which libsodium accepts it; the count stops when the weight
reaches the threshold; a transaction is authorised when the weight got there
and no signature was left uncounted (one left over is ``txBAD_AUTH_EXTRA``,
too little weight ``txBAD_AUTH``).

Every limit is 0: the comparisons are exact.
"""

from __future__ import annotations

import base64
import hashlib
import sqlite3
import struct
from typing import Dict, List, NamedTuple, Sequence, Tuple

from benchmarks.reference import sodium_verdicts

ENVELOPE_TYPE_TX = 2
TX_SUCCESS, TX_BAD_AUTH, TX_BAD_AUTH_EXTRA = 0, -6, -10


class Account(NamedTuple):
    """How an account is held: (raw key, weight) of each signer, the
    master key's weight, and the weight a payment needs."""

    signers: Tuple[Tuple[bytes, int], ...]
    master_weight: int
    threshold: int


def contents_hash(network_id: bytes, tx_xdr: bytes) -> bytes:
    """What a transaction's signatures sign: SHA-256 of the network id, the
    envelope type and the transaction's XDR."""
    return hashlib.sha256(network_id + struct.pack(">i", ENVELOPE_TYPE_TX) + tx_xdr).digest()


def keys_of(account: Account, master: bytes) -> List[Tuple[bytes, int]]:
    """The (raw key, weight) pairs that can sign for the account: the
    master key where it has weight, then the signers."""
    return ([(master, account.master_weight)] if account.master_weight else []) + list(account.signers)


def expected_code(keys: Sequence[Tuple[bytes, int]], threshold: int, accepted: Sequence[Sequence[int]]) -> int:
    """The result code plain arithmetic gives an envelope whose only
    question is authorisation.  ``accepted[i]``: the indices into ``keys``
    that end in the hint of the envelope's i-th signature and under which
    libsodium accepts it."""
    counted: set = set()
    weight = used = 0
    for mine in accepted:
        if weight >= threshold:
            break
        k = next((k for k in mine if k not in counted), None)
        if k is not None:
            counted.add(k)  # a signer counts once
            weight += keys[k][1]
            used += 1
    if weight < threshold:
        return TX_BAD_AUTH
    return TX_SUCCESS if used == len(accepted) else TX_BAD_AUTH_EXTRA


def expected_codes(envelopes: Sequence, accounts: Dict[bytes, Account], network_id: bytes) -> List[Tuple[str, int]]:
    """(txid, expected result code) of each envelope, its source in
    ``accounts``; libsodium is asked once, about every signature under
    every key that ends in its hint."""
    plans, triples = [], []
    for env in envelopes:
        master = env.tx.sourceAccount.value
        keys = keys_of(accounts[master], master)
        msg = contents_hash(network_id, env.tx.to_xdr())
        hinted = [[k for k, (pk, _) in enumerate(keys) if pk[-4:] == s.hint] for s in env.signatures]
        for s, mine in zip(env.signatures, hinted):
            triples.extend((keys[k][0], msg, s.signature) for k in mine)
        plans.append((msg.hex(), keys, accounts[master].threshold, hinted))
    verdicts = iter(sodium_verdicts(triples))
    out = []
    for txid, keys, threshold, hinted in plans:
        accepted = [[k for k in mine if next(verdicts)] for mine in hinted]
        out.append((txid, expected_code(keys, threshold, accepted)))
    return out


def result_codes(db_path: str) -> Dict[str, int]:
    """txid (hex of the contents hash) -> the result code the node stored,
    read from ``txhistory`` by sqlite3 alone: ``txresult`` is the base64 of
    a TransactionResultPair, whose code follows the 32-byte hash and the
    8-byte fee."""
    con = sqlite3.connect(f"file:{db_path}?mode=ro", uri=True)
    try:
        rows = con.execute("SELECT txid, txresult FROM txhistory").fetchall()
    finally:
        con.close()
    return {txid: struct.unpack(">i", base64.b64decode(res)[40:44])[0] for txid, res in rows}


def authorisation_differs(expected: Sequence[Tuple[str, int]], stored: Dict[str, int]) -> int:
    """How many closed envelopes break the authorisation guarantee: the
    node's stored code differs from plain arithmetic's, the node stored
    none, or plain arithmetic says the envelope was not authorised — a
    closed ledger holds no such envelope, ``check_valid`` refuses the set."""
    return sum(1 for txid, want in expected if want != TX_SUCCESS or stored.get(txid) != want)


def signer_rows_off(db_path: str, expected: Dict[str, Dict[str, int]]) -> int:
    """Rows of the ``signers`` table (sqlite3 alone) that differ from
    ``expected`` — account strkey -> {signer strkey: weight} — either way:
    missing, extra or with another weight."""
    con = sqlite3.connect(f"file:{db_path}?mode=ro", uri=True)
    try:
        rows = con.execute("SELECT accountid, publickey, weight FROM signers").fetchall()
    finally:
        con.close()
    found: Dict[str, Dict[str, int]] = {}
    for aid, pk, weight in rows:
        found.setdefault(aid, {})[pk] = weight
    want = {(a, k, w) for a, ks in expected.items() for k, w in ks.items()}
    have = {(a, k, w) for a, ks in found.items() for k, w in ks.items()}
    # a duplicated row would hide in the dicts
    return len(want ^ have) + (len(rows) - len(have))


def expected_balances(closed: list, genesis: Dict[str, int], fee: int, strkey_of) -> Dict[str, int]:
    """``reference.expected_balances`` extended to SET_OPTIONS, which moves
    nothing but the fee: native payments, account creations and option
    changes only, every one successful."""
    bal = dict(genesis)
    for rec in closed:
        for env in rec.envelopes:
            tx = env.tx
            src = strkey_of(tx.sourceAccount)
            bal[src] = bal.get(src, 0) - tx.fee
            for op in tx.operations:
                body = op.body.value
                if not hasattr(body, "destination"):
                    continue  # SET_OPTIONS
                dest = strkey_of(body.destination)
                amount = getattr(body, "amount", None)
                if amount is None:
                    amount = body.startingBalance
                bal[src] -= amount
                bal[dest] = bal.get(dest, 0) + amount
    return bal


def txs_of(closed: List, accounts: Dict[bytes, Account]) -> list:
    """The closed envelopes that an account of ``accounts`` sent once it
    was held under its signers: everything but the funding and the
    transaction that installed them (signed by the master key alone)."""
    return [
        env
        for rec in closed
        for env in rec.envelopes
        if env.tx.sourceAccount.value in accounts
        and env.tx.operations[0].body.value.__class__.__name__ != "SetOptionsOp"
    ]
