"""txhistory / txfeehistory tables (reference: TransactionFrame::storeTransaction
/ storeTransactionFee, src/transactions/TransactionFrame.cpp:497-560).

Rows keep base64 XDR blobs of the envelope, result pair, and meta — the
publish state machine reads them back out to build history checkpoint files.
"""

from __future__ import annotations

import base64
import struct
from typing import List, Optional, Tuple

from ..xdr.entries import LedgerEntry
from ..xdr.ledger import (
    LEDGER_ENTRY_CHANGES,
    LedgerEntryChangeType,
    TransactionHistoryEntry,
    TransactionHistoryResultEntry,
    TransactionMeta,
    TransactionResultPair,
)
from ..xdr.txs import TransactionEnvelope


# Both tables are keyed by where a close writes and every reader reads:
# (ledgerseq, txindex).  A close's rows arrive in ascending txindex under
# one growing ledgerseq, so the table and its one index are both appended
# to; the key's prefix serves every ``WHERE ledgerseq=?`` read and its
# order is ``ORDER BY txindex``.  Nothing looks a row up by txid, so no
# index on it: an index on a hash puts every row of a close on a random
# leaf of a tree that only grows (PERF.md section 6, PR 35).
_TX_COLUMNS = "txid, ledgerseq, txindex, txbody, txresult, txmeta"
_FEE_COLUMNS = "txid, ledgerseq, txindex, txchanges"
_TX_DDL = """CREATE TABLE {name} (
            txid      CHARACTER(64) NOT NULL,
            ledgerseq INT NOT NULL CHECK (ledgerseq >= 0),
            txindex   INT NOT NULL,
            txbody    TEXT NOT NULL,
            txresult  TEXT NOT NULL,
            txmeta    TEXT NOT NULL,
            PRIMARY KEY (ledgerseq, txindex)
        )"""
_FEE_DDL = """CREATE TABLE {name} (
            txid      CHARACTER(64) NOT NULL,
            ledgerseq INT NOT NULL CHECK (ledgerseq >= 0),
            txindex   INT NOT NULL,
            txchanges TEXT NOT NULL,
            PRIMARY KEY (ledgerseq, txindex)
        )"""
_TABLES = (
    ("txhistory", _TX_DDL, _TX_COLUMNS),
    ("txfeehistory", _FEE_DDL, _FEE_COLUMNS),
)


def drop_tx_history(db) -> None:
    for name, ddl, _ in _TABLES:
        db.execute(f"DROP TABLE IF EXISTS {name}")
        db.execute(ddl.format(name=name))


def rekey_tx_history(db) -> None:
    """Schema 1 -> 2: rebuild both tables, keyed ``(txid, ledgerseq)`` with
    a second index by ledgerseq, under the key ``drop_tx_history`` gives
    them now; every row kept, written in key order.  The caller
    (``Database.upgrade_to_current_schema``) holds the one transaction
    around it: a kill before its COMMIT leaves the old tables whole."""
    for name, ddl, columns in _TABLES:
        db.execute(ddl.format(name=f"{name}_rekeyed"))
        db.execute(
            f"INSERT INTO {name}_rekeyed ({columns}) SELECT {columns}"
            f" FROM {name} ORDER BY ledgerseq, txindex"
        )
        db.execute(f"DROP TABLE {name}")  # its indexes go with it
        db.execute(f"ALTER TABLE {name}_rekeyed RENAME TO {name}")


def transaction_row(
    tx_id: bytes,
    ledger_seq: int,
    tx_index: int,
    envelope_xdr: bytes,
    result_pair: TransactionResultPair,
    meta: TransactionMeta,
) -> Tuple:
    return (
        tx_id.hex(),
        ledger_seq,
        tx_index,
        base64.b64encode(envelope_xdr).decode(),
        base64.b64encode(result_pair.to_xdr()).decode(),
        base64.b64encode(meta.to_xdr()).decode(),
    )


def transaction_rows(
    ledger_seq: int, items: List[Tuple[int, bytes, bytes, bytes, bytes]]
) -> List[Tuple]:
    """[(tx_index, txid, envelope, result pair, meta)], the last four as
    XDR bytes -> the rows ``transaction_row`` builds one at a time, for a
    whole set in one call.

    The close encodes a set's rows here once, after the apply loop,
    instead of a hex, three ``base64`` calls and three ``.decode()`` a
    transaction.  The native `_applycore` leg does the batch in C; the
    pure-Python fallback keeps the path alive where the toolchain can't
    build the extension.  Same bytes either way
    (tests/test_txhistory_rows.py)."""
    from ..native import load_applycore

    mod = load_applycore()
    blobs = [item[1:] for item in items]
    if mod is not None:
        enc = mod.encode_history_rows(blobs)
    else:
        enc = [
            (
                t.hex(),
                base64.b64encode(b).decode(),
                base64.b64encode(r).decode(),
                base64.b64encode(m).decode(),
            )
            for t, b, r, m in blobs
        ]
    return [
        (h, ledger_seq, item[0], b, r, m) for item, (h, b, r, m) in zip(items, enc)
    ]


def fee_row(tx_id: bytes, ledger_seq: int, tx_index: int, changes) -> Tuple:
    return (
        tx_id.hex(),
        ledger_seq,
        tx_index,
        base64.b64encode(LEDGER_ENTRY_CHANGES.pack(changes)).decode(),
    )


# what LEDGER_ENTRY_CHANGES packs ahead of the entry of a list that holds
# one LEDGER_ENTRY_UPDATED: the list's length, the change's type
_ONE_UPDATED = struct.pack(">II", 1, LedgerEntryChangeType.LEDGER_ENTRY_UPDATED)


def pack_fee_changes(account: LedgerEntry) -> bytes:
    """The packed change list of one fee charge, ``[LEDGER_ENTRY_UPDATED(
    account)]``: charging a fee changes the source account and nothing
    else.  The fee pass calls this right after each store, on the snapshot
    the store left — the entry is in cache lines then; packed after the
    loop, 5,000 snapshots later, a row cost 2-3x as much (PERF.md section
    6, PR 46)."""
    return _ONE_UPDATED + account.to_xdr()


def fee_rows(ledger_seq: int, items: List[Tuple[int, bytes, bytes]]) -> List[Tuple]:
    """[(tx_index, txid, packed changes)] -> the rows ``fee_row`` builds
    one at a time, for a whole set in one call: the hex and the base-64
    natively in `_applycore` (``encode_fee_rows``), or by the loop below
    where the extension did not build.  Same bytes either way
    (tests/test_txhistory_rows.py)."""
    from ..native import load_applycore

    mod = load_applycore()
    if mod is not None:
        return mod.encode_fee_rows(ledger_seq, items)
    return [
        (t.hex(), ledger_seq, index, base64.b64encode(c).decode())
        for index, t, c in items
    ]


_TX_INSERT = f"INSERT INTO txhistory ({_TX_COLUMNS}) VALUES (?,?,?,?,?,?)"
_FEE_INSERT = f"INSERT INTO txfeehistory ({_FEE_COLUMNS}) VALUES (?,?,?,?)"


def insert_transaction_rows(db, rows: List[Tuple]) -> None:
    """Bulk path for ledger close: one executemany for the whole txset."""
    if rows:
        db.executemany(_TX_INSERT, rows)


def insert_fee_rows(db, rows: List[Tuple]) -> None:
    if rows:
        db.executemany(_FEE_INSERT, rows)


def load_transaction_history(db, ledger_seq: int) -> List[Tuple]:
    """[(envelope, result_pair)] in apply (txindex) order."""
    rows = db.query_all(
        "SELECT txbody, txresult FROM txhistory WHERE ledgerseq=? ORDER BY txindex",
        (ledger_seq,),
    )
    return [
        (
            TransactionEnvelope.from_xdr(base64.b64decode(b)),
            TransactionResultPair.from_xdr(base64.b64decode(r)),
        )
        for b, r in rows
    ]


def delete_old_entries(db, ledger_seq: int) -> None:
    db.execute("DELETE FROM txhistory WHERE ledgerseq <= ?", (ledger_seq,))
    db.execute("DELETE FROM txfeehistory WHERE ledgerseq <= ?", (ledger_seq,))
