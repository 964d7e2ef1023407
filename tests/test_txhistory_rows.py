"""The bytes of the txhistory rows: a close encodes its set's rows in one
call (`tx/history.transaction_rows`, the native `_applycore` leg or the
Python fallback) and what it hands to the insert must equal
`tx_history.transaction_row` built per transaction."""

import pytest
from test_serial_apply import close, funded, hold_under_signers, node, pay, payments_of_666_raise, sign_with

import stellar_tpu.xdr as X
from stellar_tpu.tx import testutils as T
from stellar_tpu.tx.frame import TransactionFrame


def _encoder(encoder, monkeypatch):
    from stellar_tpu import native

    if encoder == "native" and native.load_applycore() is None:
        pytest.skip("the _applycore extension did not build here")
    if encoder == "python":
        monkeypatch.setattr(native, "load_applycore", lambda: None)


@pytest.mark.parametrize("encoder", ["native", "python"])
def test_transaction_rows_equals_transaction_row(encoder, monkeypatch):
    """Blob lengths of every residue mod 3 (base64's padding), an empty
    meta, an empty set."""
    from stellar_tpu.tx import history as tx_history
    from stellar_tpu.xdr.ledger import TransactionMeta, TransactionResultPair

    _encoder(encoder, monkeypatch)
    assert tx_history.transaction_rows(9, []) == []
    meta = TransactionMeta(0, [])
    items, want = [], []
    for n in range(7):
        txid = bytes([n]) * 32
        env = bytes(range(n)) + b"\xff" * 40
        pair = TransactionResultPair(txid, X.TransactionResult(feeCharged=100 + n))
        items.append((n + 1, txid, env, pair.to_xdr(), meta.to_xdr()))
        want.append(tx_history.transaction_row(txid, 9, n + 1, env, pair, meta))
    got = tx_history.transaction_rows(9, items)
    assert got == want
    assert [[type(col) for col in row] for row in got] == [[str, int, int, str, str, str]] * 7


# -- the sets a close hands over ----------------------------------------------


def _pairs_with_a_failed_tx(app, keys, first, monkeypatch):
    txs = [pay(app, k, first + 1, keys[i ^ 1], 100) for i, k in enumerate(keys)]
    # more than the account holds: txFAILED, fee charged, empty meta
    txs[2] = pay(app, keys[2], first + 1, keys[3], 10**12)
    return txs, ["txFAILED"] + ["txSUCCESS"] * 5


def _one_tx(app, keys, first, monkeypatch):
    return [pay(app, keys[0], first + 1, keys[1], 100)], ["txSUCCESS"]


def _multisig_3_of_5(app, keys, first, monkeypatch):
    """Envelopes of three signatures each: the longest rows a payment set
    writes (`multisig5000.close`)."""
    signers = [[T.get_account("rw-signer-%d-%d" % (i, j)) for j in range(5)] for i in range(len(keys))]
    held = [hold_under_signers(app, k, first + 1, mine) for k, mine in zip(keys, signers)]
    close(app, held)
    assert [tx.get_result_code().name for tx in held] == ["txSUCCESS"] * len(keys)
    txs = [pay(app, k, first + 2, keys[i ^ 1], 100) for i, k in enumerate(keys)]
    for i, tx in enumerate(txs):
        # the last account signs with two of its five: under the threshold
        sign_with(tx, signers[i][i % 3 :][: 2 if i == len(keys) - 1 else 3])
    assert [len(tx.envelope.signatures) for tx in txs] == [3] * 5 + [2]
    return txs, ["txBAD_AUTH"] + ["txSUCCESS"] * 5


def _op_raises_internal_error(app, keys, first, monkeypatch):
    payments_of_666_raise(monkeypatch)
    txs = [pay(app, k, first + 1, keys[i ^ 1], 666 if i == 1 else 100) for i, k in enumerate(keys)]
    return txs, ["txINTERNAL_ERROR"] + ["txSUCCESS"] * 5


def _void_result_bodies(app, keys, first, monkeypatch):
    """Result bodies that are void: a signature of a stranger, and a source
    that cannot pay the fee once more above its reserve.  (A transaction
    out of sequence writes no row: the fee pass aborts the close before
    the apply loop — tests/test_serial_apply.py, `bad-seq`.)"""
    lm = app.ledger_manager
    poor = T.get_account("rw-poor")
    root = T.root_key_for(app)
    close(app, [T.tx_from_ops(app, root, 2, [T.create_account_op(poor, lm.get_min_balance(0) + 150)])])
    txs = [pay(app, k, first + 1, keys[i ^ 1], 100) for i, k in enumerate(keys[:4])]
    sign_with(txs[0], [keys[5]])
    txs.append(pay(app, poor, (lm.last_closed.header.ledgerSeq << 32) + 1, keys[0], 1))
    return txs, ["txBAD_AUTH", "txINSUFFICIENT_BALANCE"] + ["txSUCCESS"] * 3


ROW_SETS = {
    "pairs-with-a-failed-tx": _pairs_with_a_failed_tx,
    "one-tx": _one_tx,
    "multisig-3-of-5": _multisig_3_of_5,
    "op-raises-internal-error": _op_raises_internal_error,
    "bad-auth-and-insufficient-balance": _void_result_bodies,
}


@pytest.mark.parametrize("encoder", ["native", "python"])
@pytest.mark.parametrize("shape", sorted(ROW_SETS))
def test_history_rows_equal_per_tx_rows(shape, encoder, monkeypatch):
    """What a close hands to the txhistory insert — native encoder and
    fallback — equals tx_history.transaction_row built per transaction from
    the frame and the very meta object apply filled, and is what the
    database then holds."""
    from stellar_tpu.tx import history as tx_history

    _encoder(encoder, monkeypatch)
    app, clock = node(204 + (encoder == "python"))
    try:
        keys = [T.get_account("rw-%d" % i) for i in range(6)]
        first = funded(app, keys)
        txs, want_codes = ROW_SETS[shape](app, keys, first, monkeypatch)

        metas, handed = {}, []
        real_apply = TransactionFrame.apply

        def apply(self, delta, app_, meta=None, tracer=None):
            metas[self.get_contents_hash()] = meta
            return real_apply(self, delta, app_, meta, tracer)

        real_insert = tx_history.insert_transaction_rows

        def insert(db, rows):
            handed.extend(rows)
            real_insert(db, rows)

        monkeypatch.setattr(TransactionFrame, "apply", apply)
        monkeypatch.setattr(tx_history, "insert_transaction_rows", insert)
        seq, _order = close(app, txs)

        n = len(txs)
        by_index = {row[2]: row for row in handed}
        assert sorted(by_index) == list(range(1, n + 1)) and len(handed) == n
        codes = []
        for row in handed:
            (tx,) = [t for t in txs if t.get_contents_hash().hex() == row[0]]
            code = tx.get_result_code().name
            codes.append(code)
            meta = metas[tx.get_contents_hash()]
            assert row == tx_history.transaction_row(
                tx.get_contents_hash(), seq, row[2], tx.env_xdr(), tx.get_result_pair(), meta
            )
            if code != "txSUCCESS":
                assert meta.value == [] and tx.result.feeCharged == 100
        assert sorted(codes) == sorted(want_codes)
        # and what the database holds is what was handed over
        stored = app.database.query_all(
            "SELECT txid, ledgerseq, txindex, txbody, txresult, txmeta FROM txhistory"
            " WHERE ledgerseq=? ORDER BY txindex", (seq,),
        )
        assert [tuple(r) for r in stored] == [by_index[i] for i in range(1, n + 1)]
        assert app.invariants.total_violations == 0, app.invariants.dump_info()
    finally:
        app.graceful_stop()
        clock.shutdown()
