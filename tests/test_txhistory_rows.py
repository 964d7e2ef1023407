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


# -- txfeehistory: the fee pass's rows ----------------------------------------


@pytest.mark.parametrize("encoder", ["native", "python"])
def test_fee_rows_equals_fee_row(encoder, monkeypatch):
    """`fee_rows` over changes packed by `pack_fee_changes` gives the rows
    `fee_row` builds from the change list itself — accounts with and
    without signers, a home domain of every length's padding — and over raw
    blobs of every residue mod 3 what `hex` and `base64` give; an empty set."""
    import base64

    from stellar_tpu.ledger.accountframe import AccountFrame
    from stellar_tpu.tx import history as tx_history
    from stellar_tpu.xdr.ledger import LedgerEntryChange, LedgerEntryChangeType

    _encoder(encoder, monkeypatch)
    assert tx_history.fee_rows(9, []) == []
    items, want = [], []
    for n in range(7):
        txid = bytes([n]) * 32
        frame = AccountFrame(account_id=T.get_account("fr-%d" % n).get_public_key())
        account = frame.mut()
        account.balance, account.seqNum, account.homeDomain = 10**9 - n, (3 << 32) + n, "d" * n
        account.signers = [X.Signer(T.get_account("fr-s-%d" % j).get_public_key(), 1 + j) for j in range(n % 3)]
        items.append((n + 1, txid, tx_history.pack_fee_changes(frame.entry)))
        changes = [LedgerEntryChange(LedgerEntryChangeType.LEDGER_ENTRY_UPDATED, frame.entry)]
        want.append(tx_history.fee_row(txid, 9, n + 1, changes))
    got = tx_history.fee_rows(9, items)
    assert got == want
    assert [[type(col) for col in row] for row in got] == [[str, int, int, str]] * 7
    raw = [(n + 1, bytes([n]) * 32, bytes(range(n))) for n in range(7)]
    assert tx_history.fee_rows(4, raw) == [
        (txid.hex(), 4, index, base64.b64encode(blob).decode()) for index, txid, blob in raw
    ]


def reference_fee_pass(lm, txs, delta):
    """The fee pass as it was before PR 47, written out: a nested delta a
    transaction, the fee added to that delta's header, the row from the
    nested delta's own change list through `fee_row`."""
    from stellar_tpu.ledger.delta import LedgerDelta
    from stellar_tpu.tx import history as tx_history

    rows = []
    seq = lm.current.header.ledgerSeq
    db = lm.database
    with db.transaction():
        for index, tx in enumerate(txs, start=1):
            this_tx_delta = LedgerDelta(outer=delta)
            tx.reset_signature_tracker()
            tx.reset_results()
            if not tx.load_account(db):
                raise RuntimeError("Unexpected database state: missing source account")
            fee = tx.result.feeCharged
            if fee > 0:
                avail = tx.signing_account.get_balance()
                if avail < fee:
                    fee = avail
                    tx.result.feeCharged = fee
                tx.signing_account.mut().balance -= fee
                this_tx_delta.get_header().feePool += fee
            if tx.signing_account.get_seq_num() + 1 != tx.envelope.tx.seqNum:
                raise RuntimeError("Unexpected account state: bad sequence")
            tx.signing_account.set_seq_num(tx.envelope.tx.seqNum)
            tx.signing_account.store_change(this_tx_delta, db)
            rows.append(tx_history.fee_row(tx.get_contents_hash(), seq, index, this_tx_delta.get_changes()))
            this_tx_delta.commit()
        db.materialize_savepoints()
        tx_history.insert_fee_rows(db, rows)


def _several_of_one_source(app, keys, first):
    """Three transactions of one account and two of another among those of
    four more: each is charged in order, on the account as the one before
    left it."""
    txs = [pay(app, keys[0], first + n, keys[3], 10 * n) for n in (1, 2, 3)]
    txs += [pay(app, keys[1], first + n, keys[4], 7) for n in (1, 2)]
    txs += [pay(app, k, first + 1, keys[0], 5) for k in keys[2:]]
    return txs, ["txSUCCESS"] * 9


def _fee_above_the_balance(app, keys, first):
    """A fee larger than all the source holds takes all it holds."""
    lm = app.ledger_manager
    poor = T.get_account("fp-poor")
    holds = lm.get_min_balance(0) + 40
    close(app, [T.tx_from_ops(app, keys[0], first + 1, [T.create_account_op(poor, holds)])])
    poor_seq = (lm.last_closed.header.ledgerSeq << 32) + 1
    txs = [
        T.tx_from_ops(app, poor, poor_seq, [T.payment_op(keys[1], 1)], fee=holds + 1000),
        pay(app, keys[2], first + 1, keys[3], 9),
    ]
    return txs, ["txINSUFFICIENT_BALANCE", "txSUCCESS"]


def _zero_fees(app, keys, first):
    """A fee of nothing: the sequence number is taken and the row written
    all the same; between them a transaction that pays its fee."""
    txs = [
        T.tx_from_ops(app, keys[0], first + 1, [T.payment_op(keys[1], 3)], fee=0),
        pay(app, keys[2], first + 1, keys[3], 9),
        T.tx_from_ops(app, keys[4], first + 1, [T.payment_op(keys[5], 3)], fee=0),
    ]
    return txs, ["txINSUFFICIENT_FEE", "txINSUFFICIENT_FEE", "txSUCCESS"]


def _empty_set(app, keys, first):
    return [], []


def _multi_operation(app, keys, first):
    """Three operations under one fee; an operation whose source is not the
    transaction's (it signs too): only the transaction's source is charged."""
    three = T.tx_from_ops(app, keys[0], first + 1, [T.payment_op(keys[1 + j], 10 + j) for j in range(3)])
    lent = T.tx_from_ops(
        app, keys[4], first + 1, [T.payment_op(keys[0], 4), T.payment_op(keys[0], 6, source=keys[5])]
    )
    lent.add_signature(keys[5])
    return [three, lent], ["txSUCCESS"] * 2


def _multi_signer(app, keys, first):
    """Sources held under five signers, three signing: the row carries the
    account with its signers."""
    signers = [[T.get_account("fp-signer-%d-%d" % (i, j)) for j in range(5)] for i in range(len(keys))]
    close(app, [hold_under_signers(app, k, first + 1, mine) for k, mine in zip(keys, signers)])
    txs = [pay(app, k, first + 2, keys[i ^ 1], 100) for i, k in enumerate(keys)]
    for i, tx in enumerate(txs):
        sign_with(tx, signers[i][i % 3 :][:3])
    return txs, ["txSUCCESS"] * 6


FEE_SETS = {
    "several-of-one-source": _several_of_one_source,
    "fee-above-the-balance": _fee_above_the_balance,
    "zero-fees": _zero_fees,
    "empty-set": _empty_set,
    "multi-operation": _multi_operation,
    "multi-signer": _multi_signer,
}
FEE_KNOBS = {
    "as-shipped": {},
    "paranoid": {"PARANOID_MODE": True},
    "no-write-buffer": {"ENTRY_WRITE_BUFFER": False},
    "no-frame-context": {"FRAME_CONTEXT": False},
    "no-cow-snapshots": {"COW_ENTRY_SNAPSHOTS": False},
}


@pytest.mark.parametrize("knob", sorted(FEE_KNOBS))
@pytest.mark.parametrize("shape", sorted(FEE_SETS))
def test_fee_pass_equals_the_reference_loop(shape, knob):
    """The batched fee pass against `reference_fee_pass` on a second node
    under the same configuration: the delta and the header as the pass
    leaves them, `txfeehistory`'s bytes, every table, every result and fee
    charged, the ledger hash."""
    import base64
    import types

    from stellar_tpu.ledger.manager import LedgerManager
    from stellar_tpu.xdr.ledger import LEDGER_ENTRY_CHANGES

    def configure(cfg):
        for name, value in FEE_KNOBS[knob].items():
            assert hasattr(cfg, name)
            setattr(cfg, name, value)

    sides = []
    nodes = [node(206 + i, configure) for i in range(2)]
    try:
        for (app, _clock), fee_pass in zip(nodes, (LedgerManager._process_fees_seq_nums, reference_fee_pass)):
            lm = app.ledger_manager
            left = []

            def recording(self, txs, delta, fee_pass=fee_pass, left=left):
                fee_pass(self, txs, delta)
                left.append((LEDGER_ENTRY_CHANGES.pack(delta.get_changes()), delta.header_ro().to_xdr()))

            keys = [T.get_account("fp-%d" % i) for i in range(6)]
            first = funded(app, keys)
            txs, want_codes = FEE_SETS[shape](app, keys, first)
            lm._process_fees_seq_nums = types.MethodType(recording, lm)
            seq, order = close(app, txs)
            assert sorted(tx.get_result_code().name for tx in txs) == want_codes
            assert app.invariants.total_violations == 0, app.invariants.dump_info()
            sides.append({
                "left by the pass": left,
                "results": [(tx.get_result_code().name, tx.result.feeCharged) for tx in order],
                "tables": T.dump_state(app.database),
                "hash": lm.last_closed.hash,
                "fee pool": lm.last_closed.header.feePool,
            })
            fee_rows = app.database.query_all(
                "SELECT txid, txindex, txchanges FROM txfeehistory WHERE ledgerseq=? ORDER BY txindex", (seq,)
            )
            assert [(r[0], r[1]) for r in fee_rows] == [
                (tx.get_contents_hash().hex(), i) for i, tx in enumerate(order, start=1)
            ]
            sides[-1]["charged accounts"] = [
                (c.value.data.value.balance, c.value.data.value.seqNum, len(c.value.data.value.signers))
                for _txid, _index, blob in fee_rows
                for c in LEDGER_ENTRY_CHANGES.unpack(base64.b64decode(blob))
            ]
        change, reference = sides
        assert len(change["left by the pass"]) == 1
        for what in reference:
            assert change[what] == reference[what], what
        if shape == "several-of-one-source":
            # each row holds the account as its own transaction left it
            mine = [a for a, tx in zip(change["charged accounts"], order) if tx.get_source_id() == keys[0].get_public_key()]
            assert mine == [(10**9 - 100 * n, first + n, 0) for n in (1, 2, 3)]
        if shape == "fee-above-the-balance":
            assert ("txINSUFFICIENT_BALANCE", nodes[0][0].ledger_manager.get_min_balance(0) + 40) in change["results"]
            assert 0 in [balance for balance, _seq, _signers in change["charged accounts"]]
        if shape == "multi-signer":
            assert [signers for _b, _s, signers in change["charged accounts"]] == [5] * 6
    finally:
        for app, clock in nodes:
            app.graceful_stop()
            clock.shutdown()
