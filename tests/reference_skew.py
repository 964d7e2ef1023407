"""The plain reference of ``zipf1000``: per-account sequence chains, held to
the protocol's rules by plain arithmetic.

What a validator that closed a skewed stream of native payments must have
left in its database, computed from the envelopes the database stores
(``txhistory`` by ``(ledgerseq, txindex)``), its header chain
(``ledgerheaders``) and the generator's own stream, and from nothing else:

(a) every account's final balance and sequence number (a payment moves its
    amount, the source pays the envelope's fee and takes its sequence
    number; an account created in ledger L starts at ``L << 32``);
(b) per ledger, the apply order the protocol fixes (``TxSetFrame.cpp:93-131``:
    batch *d* holds every account's *d*-th transaction of the set by
    sequence number; inside a batch by full hash XOR the set's contents
    hash, which is SHA-256(previous ledger hash ‖ envelopes in full-hash
    order)), against the stored ``txindex`` order;
(c) per account, the closed payments are a gapless prefix of what the stream
    offered from it, in the stream's order, byte for byte;
(d) the shape: the share of the window's closed payments sent by the
    top-ranked account and by the top 1 % of ranks, against the distribution's.

The band of (d).  A window that closes n payments drew them from the stream,
so a share is binomial around the distribution's p: ``Z`` = 5 standard
deviations (a run in 10^6 strays that far).  The closed loop holds at most
``backlog`` offered payments back at each edge of the window, and which
accounts they belong to is the surge filter's choice (equal fees fall to the
account id), so a share may also be off by ``backlog / n`` either way.  At
full size (n ~ 50,000, backlog 2,000) the top account's 9.8 % may read
5.1-14.5 % and the top 1 %'s 51.8 % 46.7-56.9 %; a generator gone uniform
reads 0.01 % and 1 %.

Plain integers, ``hashlib``, ``struct``, ``sqlite3``.  Nothing is imported
from ``stellar_tpu``; ``tests/reference_skew.py`` is this file, byte for byte
(tier-1 compares them).
"""

from __future__ import annotations

import base64
import hashlib
import math
import sqlite3
import statistics
import struct
from typing import Dict, List, NamedTuple, Sequence, Tuple

ROWS = (
    "seqnums_off_plain_arithmetic",
    "balances_off_stored_envelopes",
    "apply_order_differs",
    "account_chain_gaps",
    "hot_share_off",
)
Z = 5.0


class Tx(NamedTuple):
    source: bytes
    fee: int
    seq: int
    ops: tuple  # ("create" | "pay", destination, amount)
    full_hash: bytes
    blob: bytes


# -- the distribution --------------------------------------------------------


def zipf_weights(n: int, constant: float) -> List[float]:
    """P(rank k) ∝ k^-constant, k = 1..n (YCSB's zipfian request
    distribution), as probabilities by rank."""
    w = [k**-constant for k in range(1, n + 1)]
    total = math.fsum(w)
    return [x / total for x in w]


def hot_ranks(n: int) -> int:
    """How many ranks the top 1 % are."""
    return max(1, n // 100)


def top_shares(n: int, constant: float) -> Tuple[float, float]:
    """The distribution's share of the top rank and of the top 1 % of ranks."""
    p = zipf_weights(n, constant)
    return p[0], math.fsum(p[: hot_ranks(n)])


def band(p: float, n: int, backlog: int) -> Tuple[float, float]:
    """(low, high) a share of expectation ``p`` over ``n`` closed payments
    may read; see the module's text."""
    if n <= 0:
        return 0.0, 1.0
    room = Z * math.sqrt(p * (1.0 - p) / n) + backlog / n
    return p - room, p + room


def hot_shares(sources: Sequence[bytes], ranks: Sequence[bytes]) -> Tuple[float, float]:
    """The share of ``sources`` that is the top-ranked account, and the
    share that is one of the top 1 % of ``ranks``."""
    if not sources:
        return 0.0, 0.0
    top = set(ranks[: hot_ranks(len(ranks))])
    return (
        sum(1 for s in sources if s == ranks[0]) / len(sources),
        sum(1 for s in sources if s in top) / len(sources),
    )


def hot_share_off(sources: Sequence[bytes], ranks: Sequence[bytes], constant: float, backlog: int) -> Tuple[int, str]:
    """1 where either share of (d) lies outside its band, else 0; and the
    numbers, for the row's detail."""
    got = hot_shares(sources, ranks)
    want = top_shares(len(ranks), constant)
    off, said = 0, []
    for name, g, p in zip(("top account", "top 1 %"), got, want):
        lo, hi = band(p, len(sources), backlog)
        off |= not lo <= g <= hi
        said.append("%s %.4f in [%.4f, %.4f] around %.4f" % (name, g, lo, hi, p))
    return int(off), "; ".join(said) + " of %d" % len(sources)


# -- envelopes, walked with struct ----------------------------------------------


def _key(data: bytes, at: int) -> Tuple[bytes, int]:
    if struct.unpack_from(">i", data, at)[0] != 0:
        raise ValueError("a public key that is not ed25519")
    return data[at + 4 : at + 36], at + 36


def parse(blob: bytes) -> Tx:
    """A TransactionEnvelope of CREATE_ACCOUNT or native PAYMENT operations
    without time bounds, memo or operation sources: the only ones these
    deployments make."""
    source, at = _key(blob, 0)
    fee, seq, bounds, memo, n_ops = struct.unpack_from(">IqIiI", blob, at)
    at += 24
    if bounds or memo:
        raise ValueError("time bounds or a memo: not one of this deployment's")
    ops = []
    for _ in range(n_ops):
        has_source, kind = struct.unpack_from(">Ii", blob, at)
        at += 8
        if has_source or kind not in (0, 1):
            raise ValueError(f"operation type {kind}: not one of this deployment's")
        dest, at = _key(blob, at)
        if kind == 1:
            if struct.unpack_from(">i", blob, at)[0] != 0:
                raise ValueError("a payment that is not native")
            at += 4
        ops.append(("pay" if kind else "create", dest, struct.unpack_from(">q", blob, at)[0]))
        at += 8
    return Tx(source, fee, seq, tuple(ops), hashlib.sha256(blob).digest(), blob)


def raw_key(strkey: str) -> bytes:
    """The 32 bytes inside an account id as the database spells it."""
    return base64.b32decode(strkey)[1:33]


# -- what the database holds ------------------------------------------------------


def read_ledgers(db_path: str) -> Tuple[Dict[int, List[Tx]], Dict[int, bytes], Dict[bytes, Tuple[int, int]]]:
    """-> (ledger -> its transactions in ``txindex`` order, ledger -> the
    hash of the ledger before it, account -> (balance, seqnum))."""
    con = sqlite3.connect(f"file:{db_path}?mode=ro", uri=True)
    try:
        ledgers: Dict[int, List[Tx]] = {}
        for seq, body in con.execute("SELECT ledgerseq, txbody FROM txhistory ORDER BY ledgerseq, txindex"):
            ledgers.setdefault(seq, []).append(parse(base64.b64decode(body)))
        previous = {seq: bytes.fromhex(h) for seq, h in con.execute("SELECT ledgerseq, prevhash FROM ledgerheaders")}
        accounts = {
            raw_key(k): (balance, seqnum)
            for k, balance, seqnum in con.execute("SELECT accountid, balance, seqnum FROM accounts")
        }
    finally:
        con.close()
    return ledgers, previous, accounts


# -- the rules -----------------------------------------------------------------------


def plain_accounts(ledgers: Dict[int, List[Tx]], genesis: Dict[bytes, Tuple[int, int]]) -> Dict[bytes, List[int]]:
    """(a): account -> [balance, seqnum] after every stored transaction, in
    order, from ``genesis`` (account -> (balance, seqnum))."""
    state = {k: list(v) for k, v in genesis.items()}
    for seq in sorted(ledgers):
        for tx in ledgers[seq]:
            src = state[tx.source]
            src[0] -= tx.fee
            src[1] = tx.seq
            for kind, dest, amount in tx.ops:
                src[0] -= amount
                if kind == "create":
                    state[dest] = [amount, seq << 32]
                else:
                    state[dest][0] += amount
    return state


def apply_order(txs: Sequence[Tx], previous: bytes) -> List[Tx]:
    """(b): the order the protocol applies a set in."""
    by_hash = sorted(txs, key=lambda t: t.full_hash)
    h = hashlib.sha256(previous)
    for t in by_hash:
        h.update(t.blob)
    x = int.from_bytes(h.digest(), "big")
    chains: Dict[bytes, List[Tx]] = {}
    for t in txs:
        chains.setdefault(t.source, []).append(t)
    batches: Dict[int, List[Tx]] = {}
    for chain in chains.values():
        for d, t in enumerate(sorted(chain, key=lambda t: t.seq)):
            batches.setdefault(d, []).append(t)
    out: List[Tx] = []
    for d in sorted(batches):
        out.extend(sorted(batches[d], key=lambda t: int.from_bytes(t.full_hash, "big") ^ x))
    return out


def chain_gaps(ledgers: Dict[int, List[Tx]], stream: Sequence[bytes]) -> Tuple[int, int]:
    """(c): -> (accounts whose closed payments are not a gapless prefix of
    what the stream offered from them, in its order, byte for byte;
    accounts that closed a payment)."""
    offered: Dict[bytes, List[bytes]] = {}
    for blob in stream:
        offered.setdefault(blob[4:36], []).append(blob)
    closed: Dict[bytes, List[bytes]] = {}
    for seq in sorted(ledgers):
        for tx in ledgers[seq]:
            if tx.ops[0][0] == "pay":
                closed.setdefault(tx.source, []).append(tx.blob)
    bad = sum(1 for k, blobs in closed.items() if offered.get(k, [])[: len(blobs)] != blobs)
    return bad, len(closed)


def shape(ledgers: Dict[int, List[Tx]], first: int, last: int) -> dict:
    """What the closed sets of ledgers ``first``..``last`` looked like:
    medians a ledger, counted from the stored envelopes."""
    accounts, longest, widths = [], [], []
    for seq in range(first, last + 1):
        txs = ledgers.get(seq, [])
        if not txs:
            continue
        count: Dict[bytes, int] = {}
        for t in txs:
            count[t.source] = count.get(t.source, 0) + 1
        accounts.append(len(count))
        longest.append(max(count.values()))
        widths.append(len(txs))
    if not widths:
        return {"ledgers": 0}
    return {
        "ledgers": len(widths), "txs_per_ledger": statistics.median(widths),
        "source_accounts_per_ledger": statistics.median(accounts),
        "longest_chain_per_ledger": statistics.median(longest), "longest_chain_max": max(longest),
    }


def compare(
    db_path: str, genesis: Dict[bytes, Tuple[int, int]], stream: Sequence[bytes], ranks: Sequence[bytes],
    constant: float, backlog: int, window: Tuple[int, int],
) -> Tuple[Dict[str, int], Dict[str, str], dict]:
    """-> (row -> value, row -> detail, the window's shape).  ``genesis``:
    the accounts before ledger 2; ``stream``: every envelope offered, in
    order; ``ranks``: the accounts by rank, the hottest first; ``window``:
    the first and last ledger closed inside the timed window."""
    ledgers, previous, stored = read_ledgers(db_path)
    want = plain_accounts(ledgers, genesis)
    rows, detail = {}, {}
    keys = set(want) | set(stored)
    rows[ROWS[0]] = sum(1 for k in keys if k not in want or k not in stored or want[k][1] != stored[k][1])
    rows[ROWS[1]] = sum(1 for k in keys if k not in want or k not in stored or want[k][0] != stored[k][0])
    detail[ROWS[0]] = detail[ROWS[1]] = "of %d accounts" % len(keys)
    rows[ROWS[2]] = sum(
        1 for seq, txs in ledgers.items()
        if seq not in previous or [t.full_hash for t in apply_order(txs, previous[seq])] != [t.full_hash for t in txs]
    )
    detail[ROWS[2]] = "of %d ledgers with transactions" % len(ledgers)
    rows[ROWS[3]], n = chain_gaps(ledgers, stream)
    detail[ROWS[3]] = "of %d accounts that closed a payment" % n
    first, last = window
    sources = [
        t.source for seq in range(first, last + 1) for t in ledgers.get(seq, []) if t.ops[0][0] == "pay"
    ]
    rows[ROWS[4]], detail[ROWS[4]] = hot_share_off(sources, ranks, constant, backlog)
    seen = shape(ledgers, first, last)
    seen["top_account_share"], seen["top_1pct_share"] = hot_shares(sources, ranks)
    return rows, detail, seen
