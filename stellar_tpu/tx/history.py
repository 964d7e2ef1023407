"""txhistory / txfeehistory tables (reference: TransactionFrame::storeTransaction
/ storeTransactionFee, src/transactions/TransactionFrame.cpp:497-560).

Rows keep base64 XDR blobs of the envelope, result pair, and meta — the
publish state machine reads them back out to build history checkpoint files.
"""

from __future__ import annotations

import base64
from typing import List, Optional, Tuple

from ..xdr.ledger import (
    LEDGER_ENTRY_CHANGES,
    TransactionHistoryEntry,
    TransactionHistoryResultEntry,
    TransactionMeta,
    TransactionResultPair,
)
from ..xdr.txs import TransactionEnvelope


def drop_tx_history(db) -> None:
    db.execute("DROP TABLE IF EXISTS txhistory")
    db.execute("DROP TABLE IF EXISTS txfeehistory")
    db.execute(
        """CREATE TABLE txhistory (
            txid      CHARACTER(64) NOT NULL,
            ledgerseq INT NOT NULL CHECK (ledgerseq >= 0),
            txindex   INT NOT NULL,
            txbody    TEXT NOT NULL,
            txresult  TEXT NOT NULL,
            txmeta    TEXT NOT NULL,
            PRIMARY KEY (txid, ledgerseq)
        )"""
    )
    db.execute("CREATE INDEX histbyseq ON txhistory (ledgerseq)")
    db.execute(
        """CREATE TABLE txfeehistory (
            txid      CHARACTER(64) NOT NULL,
            ledgerseq INT NOT NULL CHECK (ledgerseq >= 0),
            txindex   INT NOT NULL,
            txchanges TEXT NOT NULL,
            PRIMARY KEY (txid, ledgerseq)
        )"""
    )
    db.execute("CREATE INDEX histfeebyseq ON txfeehistory (ledgerseq)")


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


_TX_INSERT = (
    "INSERT INTO txhistory (txid, ledgerseq, txindex, txbody, txresult, txmeta)"
    " VALUES (?,?,?,?,?,?)"
)
_FEE_INSERT = (
    "INSERT INTO txfeehistory (txid, ledgerseq, txindex, txchanges)"
    " VALUES (?,?,?,?)"
)


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
