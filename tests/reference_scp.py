"""A plain reference for the consensus side: what a committee's flood of
signed SCP statements must do to a node that follows it.

Nothing here comes from ``stellar_tpu/scp``, ``herder`` or ``crypto``: the
statements are packed by hand (``struct``), verdicts are libsodium's through
``ctypes``, hashes are ``hashlib``'s, and federated voting is set arithmetic
over nested quorum sets.  ``benchmarks/reference_scp.py`` is a copy of this
file (a tier-1 test holds the two equal); the benchmark decides ``correct``
with it on what the timed path produced.

The data it is given is the traffic generator's own script, as plain tuples:

* a quorum set is ``(threshold, (public key, ...), (inner quorum set, ...))``;
* a value is the XDR of ``StellarValue`` as bytes (``stellar_value`` packs one);
* a statement is one of
  ``("NOMINATE", qset_hash, votes, accepted)``,
  ``("PREPARE", qset_hash, ballot, prepared, prepared_prime, nC, nP)``,
  ``("CONFIRM", qset_hash, nPrepared, commit, nP)``,
  ``("EXTERNALIZE", commit, nP, qset_hash)``
  with a ballot ``(counter, value)`` or None;
* a delivery is ``Delivery(author, slot, k, statement, signature, forged)``:
  the author's k-th statement of the slot as the peer delivers it.

Three parts:

(a) ``payload`` / ``verdicts``: the bytes a validator signs
    (``networkID ‖ ENVELOPE_TYPE_SCP ‖ statement``, the reference's
    ``HerderImpl::verifyEnvelope``) and libsodium's verdict on each delivery;
(b) ``is_slice`` / ``is_v_blocking`` / ``quorum_within``: a quorum slice, a
    v-blocking set and the largest quorum inside a set of nodes;
(c) ``slot_outcome``: from a slot's deliveries in order, the value that may
    be externalized and the first delivery at which a node with the
    watcher's quorum set can have confirmed it — the first at which the
    nodes that have accepted ``commit`` for one value (their latest valid
    statement is a CONFIRM or an EXTERNALIZE of it) contain a quorum that
    holds a slice of the watcher's quorum set.
"""

from __future__ import annotations

import ctypes
import ctypes.util
import hashlib
import struct
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

ENVELOPE_TYPE_SCP = 1
KEY_TYPE_ED25519 = 0
ST_PREPARE, ST_CONFIRM, ST_EXTERNALIZE, ST_NOMINATE = 0, 1, 2, 3


class Delivery(NamedTuple):
    author: bytes  # raw ed25519 public key
    slot: int
    k: int  # the author's k-th statement of the slot
    statement: tuple
    signature: bytes
    forged: bool  # the generator corrupted this signature


# -- (a) bytes and verdicts ---------------------------------------------------


def _u32(n: int) -> bytes:
    return struct.pack(">I", n)


def _opaque(b: bytes) -> bytes:
    return _u32(len(b)) + b + b"\x00" * (-len(b) % 4)


def _key(pk: bytes) -> bytes:
    return struct.pack(">i", KEY_TYPE_ED25519) + pk


def _ballot(b: tuple) -> bytes:
    return _u32(b[0]) + _opaque(b[1])


def _maybe_ballot(b: Optional[tuple]) -> bytes:
    return _u32(0) if b is None else _u32(1) + _ballot(b)


def _values(vs: Sequence[bytes]) -> bytes:
    return _u32(len(vs)) + b"".join(_opaque(v) for v in vs)


def stellar_value(tx_set_hash: bytes, close_time: int, upgrades: Sequence[bytes] = ()) -> bytes:
    return tx_set_hash + struct.pack(">Q", close_time) + _values(upgrades) + struct.pack(">i", 0)


def empty_tx_set_hash(previous_ledger_hash: bytes) -> bytes:
    """Contents hash of a transaction set with no transactions."""
    return hashlib.sha256(previous_ledger_hash).digest()


def pack_qset(qset: tuple) -> bytes:
    threshold, validators, inner = qset
    return (
        _u32(threshold)
        + _u32(len(validators)) + b"".join(_key(v) for v in validators)
        + _u32(len(inner)) + b"".join(pack_qset(q) for q in inner)
    )


def qset_hash(qset: tuple) -> bytes:
    return hashlib.sha256(pack_qset(qset)).digest()


def pack_pledges(st: tuple) -> bytes:
    kind = st[0]
    if kind == "NOMINATE":
        _, qh, votes, accepted = st
        return struct.pack(">i", ST_NOMINATE) + qh + _values(votes) + _values(accepted)
    if kind == "PREPARE":
        _, qh, ballot, prepared, prime, n_c, n_p = st
        return (
            struct.pack(">i", ST_PREPARE) + qh + _ballot(ballot)
            + _maybe_ballot(prepared) + _maybe_ballot(prime) + _u32(n_c) + _u32(n_p)
        )
    if kind == "CONFIRM":
        _, qh, n_prepared, commit, n_p = st
        return struct.pack(">i", ST_CONFIRM) + qh + _u32(n_prepared) + _ballot(commit) + _u32(n_p)
    if kind == "EXTERNALIZE":
        _, commit, n_p, qh = st
        return struct.pack(">i", ST_EXTERNALIZE) + _ballot(commit) + _u32(n_p) + qh
    raise ValueError(f"unknown statement {kind!r}")


def pack_statement(author: bytes, slot: int, st: tuple) -> bytes:
    return _key(author) + struct.pack(">Q", slot) + pack_pledges(st)


def payload(network_id: bytes, d: Delivery) -> bytes:
    """What the author signs."""
    return network_id + struct.pack(">i", ENVELOPE_TYPE_SCP) + pack_statement(d.author, d.slot, d.statement)


def pack_envelope(d: Delivery) -> bytes:
    return pack_statement(d.author, d.slot, d.statement) + _opaque(d.signature)


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


def verdicts(network_id: bytes, deliveries: Sequence[Delivery]) -> List[bool]:
    """libsodium's verdict on each delivery's signature."""
    fn = _sodium().crypto_sign_verify_detached
    fn.argtypes = [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_ulonglong, ctypes.c_char_p]
    fn.restype = ctypes.c_int
    out = []
    for d in deliveries:
        msg = payload(network_id, d)
        ok = len(d.author) == 32 and len(d.signature) == 64
        out.append(ok and fn(d.signature, msg, len(msg), d.author) == 0)
    return out


# -- (b) quorum arithmetic ----------------------------------------------------


def is_slice(qset: tuple, nodes: set) -> bool:
    """``nodes`` satisfies ``threshold`` entries of ``qset``."""
    threshold, validators, inner = qset
    met = sum(1 for v in validators if v in nodes) + sum(1 for q in inner if is_slice(q, nodes))
    return met >= threshold


def is_v_blocking(qset: tuple, nodes: set) -> bool:
    """``nodes`` meets every slice of ``qset``: it hits more entries than the
    quorum set can lose."""
    threshold, validators, inner = qset
    if threshold == 0:
        return False
    hit = sum(1 for v in validators if v in nodes) + sum(1 for q in inner if is_v_blocking(q, nodes))
    return hit > len(validators) + len(inner) - threshold


def quorum_within(nodes: set, qset_of: Dict[bytes, tuple]) -> set:
    """The largest subset of ``nodes`` in which every member has a slice."""
    nodes = set(nodes)
    while True:
        kept = {n for n in nodes if n in qset_of and is_slice(qset_of[n], nodes)}
        if len(kept) == len(nodes):
            return kept
        nodes = kept


def transitive_quorum(local: tuple, qsets: Dict[bytes, tuple]) -> set:
    """Every node the local quorum set reaches through quorum sets."""
    seen, todo = set(), [local]
    while todo:
        _, validators, inner = todo.pop()
        todo.extend(inner)
        for v in validators:
            if v not in seen:
                seen.add(v)
                if v in qsets:
                    todo.append(qsets[v])
    return seen


# -- (c) what a slot's flood lets a follower decide --------------------------


class Outcome(NamedTuple):
    value: Optional[bytes]  # the value that may be externalized
    index: Optional[int]  # first delivery at which it can be confirmed
    valid_before: int  # valid deliveries up to and including ``index``


def _accepted_commit(st: tuple) -> Optional[bytes]:
    """The value a statement says its author accepted ``commit`` for."""
    if st[0] == "CONFIRM":
        return st[3][1]
    if st[0] == "EXTERNALIZE":
        return st[1][1]
    return None


def slot_outcome(
    deliveries: Sequence[Delivery], ok: Sequence[bool], local: tuple, qsets: Dict[bytes, tuple]
) -> Outcome:
    """Walk a slot's deliveries in order; ``ok`` is their verdicts.  An
    author that has externalized stands alone (its quorum set is itself)."""
    committed: Dict[bytes, bytes] = {}  # author -> value it accepted commit for
    own: Dict[bytes, tuple] = {}  # the quorum set its latest statement names
    valid = 0
    for i, (d, good) in enumerate(zip(deliveries, ok)):
        if not good:
            continue
        valid += 1
        value = _accepted_commit(d.statement)
        if value is None:
            continue
        committed[d.author] = value
        own[d.author] = (1, (d.author,), ()) if d.statement[0] == "EXTERNALIZE" else qsets[d.author]
        for_value = {a for a, v in committed.items() if v == value}
        if is_slice(local, quorum_within(for_value, own)):
            return Outcome(value, i, valid)
    return Outcome(None, None, valid)


def statement_k(st: tuple) -> int:
    """Where a statement of the committee's script stands in its author's
    sequence, from its content alone (``SEQUENCE`` below)."""
    kind = st[0]
    if kind == "NOMINATE":
        return 0 if len(st[2]) == 1 else (1 if not st[3] else 2)
    if kind == "PREPARE":
        return 3 if st[3] is None else (4 if st[5] == 0 else 5)
    return 6 if kind == "CONFIRM" else 7


# the committee's script: an author's statements of one slot, in order.  x is
# the first round leader's value, y the second's (a later closeTime); both are
# accepted and the composite the ballots run on is y.
SEQUENCE = (
    "NOMINATE votes=[x]",
    "NOMINATE votes=[x,y]",
    "NOMINATE votes=[x,y] accepted=[x,y]",
    "PREPARE b=(1,y)",
    "PREPARE b=(1,y) p=(1,y)",
    "PREPARE b=(1,y) p=(1,y) nC=1 nP=1",
    "CONFIRM nPrepared=1 commit=(1,y) nP=1",
    "EXTERNALIZE commit=(1,y) nP=1",
)


def script_statement(k: int, qh: bytes, x: bytes, y: bytes) -> tuple:
    """The k-th statement of ``SEQUENCE`` for an author whose quorum set
    hashes to ``qh``."""
    both = tuple(sorted((x, y)))
    b = (1, y)
    return (
        ("NOMINATE", qh, (x,), ()),
        ("NOMINATE", qh, both, ()),
        ("NOMINATE", qh, both, both),
        ("PREPARE", qh, b, None, None, 0, 0),
        ("PREPARE", qh, b, b, None, 0, 0),
        ("PREPARE", qh, b, b, None, 1, 1),
        ("CONFIRM", qh, 1, b, 1),
        ("EXTERNALIZE", b, 1, qh),
    )[k]
