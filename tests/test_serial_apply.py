"""The one way a ledger is applied: `LedgerManager._apply_transactions`.

Two suites over real closes of a standalone node:

- `test_serial_apply_outcomes`: what the loop promises about a transaction
  that fails, is not authorised, raises, or cannot be unwound, about signer
  rows, and about several transactions of one account in one set.
- `test_closes_equal_the_reference_apply`: accounts, fee pool and result
  codes after every close against `reference_apply.Ledger`, plain arithmetic
  that shares none of the apply path, over the shapes of traffic a payment
  network sends."""

import base64
import random

import pytest
from reference_apply import Ledger, Tx

import stellar_tpu.xdr as X
from stellar_tpu.ledger.accountframe import AccountFrame
from stellar_tpu.tx import testutils as T
from stellar_tpu.tx.frame import TransactionFrame

START = 10**9  # what a funded account holds
FEE = 100


def node(instance, configure=None):
    from stellar_tpu.main.application import Application
    from stellar_tpu.util.clock import VIRTUAL_TIME, VirtualClock

    clock = VirtualClock(VIRTUAL_TIME)
    cfg = T.get_test_config(instance)
    cfg.HTTP_PORT = 0
    if configure is not None:
        configure(cfg)
    return Application.create(clock, cfg, new_db=True), clock


def close(app, txs):
    """Close one ledger holding ``txs`` -> (its sequence number, the frames
    in the order consensus fixes for the apply)."""
    from stellar_tpu.herder.ledgerclose import LedgerCloseData
    from stellar_tpu.herder.txset import TxSetFrame
    from stellar_tpu.xdr.ledger import StellarValue

    lm = app.ledger_manager
    txset = TxSetFrame(lm.last_closed.hash, list(txs))
    txset.sort_for_hash()
    order = txset.sort_for_apply()
    value = StellarValue(txset.get_contents_hash(), lm.last_closed.header.scpValue.closeTime + 5, [], 0)
    lm.close_ledger(LedgerCloseData(lm.current.header.ledgerSeq, txset, value))
    return lm.last_closed.header.ledgerSeq, order


def funded(app, keys, balance=START):
    """Create ``keys`` from the root account -> the sequence number a new
    account's first transaction follows."""
    root = T.root_key_for(app)
    seq = AccountFrame.load_account(root.get_public_key(), app.database).get_seq_num()
    close(app, [T.tx_from_ops(app, root, seq + 1, [T.create_account_op(k, balance) for k in keys])])
    return app.ledger_manager.last_closed.header.ledgerSeq << 32


def pay(app, source, seq, dest, amount):
    return T.tx_from_ops(app, source, seq, [T.payment_op(dest, amount)])


def hold_under_signers(app, key, seq, signers, threshold=3):
    """One transaction that puts ``key``'s account under ``signers`` (weight
    1 each; master weight 0, every threshold ``threshold``): a SET_OPTIONS
    a signer, the last of them carrying the weights, as `multisig5000`'s
    set-up does."""
    last = len(signers) - 1
    return T.tx_from_ops(app, key, seq, [
        T.set_options_op(
            signer=X.Signer(s.get_public_key(), 1),
            **(dict(master_weight=0, low=threshold, med=threshold, high=threshold) if j == last else {}),
        )
        for j, s in enumerate(signers)
    ])


def sign_with(tx, signers):
    """Replace the envelope's signatures (the master key's, from
    `tx_from_ops`) by those of ``signers``."""
    tx.envelope.signatures.clear()
    for s in signers:
        tx.add_signature(s)
    return tx


def payments_of_666_raise(monkeypatch):
    """From here on a payment of 666 raises inside its operation's body."""
    from stellar_tpu.tx.ops_payment import PaymentOpFrame

    real = PaymentOpFrame.do_apply

    def do_apply(self, metrics, delta, lm):
        if self.payment.amount == 666:
            raise KeyError("a fault in an operation's body")
        return real(self, metrics, delta, lm)

    monkeypatch.setattr(PaymentOpFrame, "do_apply", do_apply)


def codes_of(txs):
    return [tx.get_result_code().name for tx in txs]


def accounts_of(app):
    return {
        aid: (balance, seq)
        for aid, balance, seq in app.database.query_all("SELECT accountid, balance, seqnum FROM accounts")
    }


def history_of(app, seq):
    """txid (hex) -> (txindex, the decoded meta's operations) of ledger ``seq``."""
    from stellar_tpu.xdr.ledger import TransactionMeta

    return {
        txid: (index, TransactionMeta.from_xdr(base64.b64decode(meta)).value)
        for txid, index, meta in app.database.query_all(
            "SELECT txid, txindex, txmeta FROM txhistory WHERE ledgerseq=?", (seq,)
        )
    }


# -- what the loop promises ---------------------------------------------------


def _failed_after_partners_payment(app, monkeypatch):
    """A transaction that fails after its partner's payment was stored rolls
    back to the partner's store, not to the line from before the apply (PR
    25 found the threaded plane leaving the stale line; the close's cache
    invariant then raised)."""
    from test_framecontext import plain_reference_config

    ref, ref_clock = node(201, plain_reference_config)
    try:
        sets = []
        for a in (app, ref):
            keys = [T.get_account("fl-%d" % i) for i in range(12)]
            first = funded(a, keys)
            # in every pair the even account pays more than it holds
            txs = [pay(a, k, first + 1, keys[i ^ 1], 100 if i & 1 else 10**12) for i, k in enumerate(keys)]
            seq, _order = close(a, txs)
            sets.append(txs)
        txs = sets[0]
        assert codes_of(txs) == ["txFAILED", "txSUCCESS"] * 6
        at = history_of(app, seq)
        index = [at[tx.get_contents_hash().hex()][0] for tx in txs]
        # the case at stake: a failure applied after its partner's store
        assert any(index[i] > index[i + 1] for i in range(0, 12, 2))
        held = accounts_of(app)
        for i, k in enumerate(keys):
            assert held[k.get_strkey_public()] == (START - FEE + (-100 if i & 1 else 100), first + 1)
        assert app.invariants.total_violations == 0, app.invariants.dump_info()
        assert app.ledger_manager.last_closed.hash == ref.ledger_manager.last_closed.hash
        assert T.dump_state(app.database) == T.dump_state(ref.database)
    finally:
        ref.graceful_stop()
        ref_clock.shutdown()


def _refused_at_apply(app, build, code):
    """One transaction of the set is refused by the validity check at
    apply: its fee is charged, its sequence number taken, nothing else of
    it stays, its history row holds an empty meta, and the close goes on."""
    keys = [T.get_account("rf-%d" % i) for i in range(4)]
    first = funded(app, keys)
    bad = build(app, keys, first)
    good = pay(app, keys[2], first + 1, keys[3], 7)
    seq, _order = close(app, [bad, good])
    assert codes_of([bad, good]) == [code, "txSUCCESS"]
    held = accounts_of(app)
    assert held[keys[0].get_strkey_public()] == (START - FEE, first + 1)
    assert held[keys[1].get_strkey_public()] == (START, first)
    assert held[keys[3].get_strkey_public()] == (START + 7, first)
    assert history_of(app, seq)[bad.get_contents_hash().hex()][1] == []
    assert app.invariants.total_violations == 0, app.invariants.dump_info()
    return keys, first, bad


def _bad_auth(app, monkeypatch):
    # signed by an account that is no signer of the source
    _refused_at_apply(
        app, lambda app, keys, first: sign_with(pay(app, keys[0], first + 1, keys[1], 5), [keys[1]]), "txBAD_AUTH"
    )


def _underfunded(app, monkeypatch):
    _keys, _first, bad = _refused_at_apply(
        app, lambda app, keys, first: pay(app, keys[0], first + 1, keys[1], 10**12), "txFAILED"
    )
    assert T.inner_op_code(bad).name == "PAYMENT_UNDERFUNDED"


def _op_raises(app, monkeypatch):
    payments_of_666_raise(monkeypatch)
    _refused_at_apply(app, lambda app, keys, first: pay(app, keys[0], first + 1, keys[1], 666), "txINTERNAL_ERROR")


def _bad_seq(app, monkeypatch):
    """A transaction out of sequence never reaches the apply loop: admission
    gives it txBAD_SEQ, a set that holds it does not validate, and a close
    fed the set all the same aborts in the fee pass and leaves the node
    where it was."""
    from stellar_tpu.ledger.entryframe import entry_cache_of

    keys = [T.get_account("bs-%d" % i) for i in range(2)]
    first = funded(app, keys)
    lm = app.ledger_manager
    skipped = pay(app, keys[0], first + 2, keys[1], 5)
    assert not skipped.check_valid(app) and codes_of([skipped]) == ["txBAD_SEQ"]
    before = (lm.last_closed.hash, accounts_of(app))
    with pytest.raises(RuntimeError, match="bad sequence"):
        close(app, [skipped, pay(app, keys[1], first + 1, keys[0], 5)])
    assert (lm.last_closed.hash, accounts_of(app)) == before
    assert not entry_cache_of(app.database)._map
    # and the node closes the next, valid, set
    good = [pay(app, keys[0], first + 1, keys[1], 5), pay(app, keys[0], first + 2, keys[1], 5)]
    close(app, good)
    assert codes_of(good) == ["txSUCCESS"] * 2


def _unrollbackable_write(app, monkeypatch):
    """Rows written under no savepoint cannot be unwound: the loop lets the
    exception through instead of recording txINTERNAL_ERROR, the close
    aborts, the entry cache is cleared, the last closed ledger stays."""
    from stellar_tpu.database.database import UnrollbackableWrite
    from stellar_tpu.ledger.entryframe import entry_cache_of

    keys = [T.get_account("ur-%d" % i) for i in range(4)]
    first = funded(app, keys)
    lm = app.ledger_manager
    txs = [pay(app, k, first + 1, keys[i ^ 1], 9) for i, k in enumerate(keys)]
    real = TransactionFrame.apply

    def apply(self, delta, app_, meta=None, tracer=None):
        if self is txs[2]:
            raise UnrollbackableWrite("rows written under no savepoint")
        return real(self, delta, app_, meta, tracer)

    monkeypatch.setattr(TransactionFrame, "apply", apply)
    before = (lm.last_closed.hash, lm.last_closed.header.ledgerSeq, accounts_of(app))
    with pytest.raises(UnrollbackableWrite):
        close(app, txs)
    assert (lm.last_closed.hash, lm.last_closed.header.ledgerSeq, accounts_of(app)) == before
    assert not entry_cache_of(app.database)._map
    assert app.database.query_all("SELECT COUNT(*) FROM txhistory WHERE ledgerseq=?", (before[1] + 1,)) == [(0,)]
    monkeypatch.setattr(TransactionFrame, "apply", real)
    again = [pay(app, k, first + 1, keys[i ^ 1], 9) for i, k in enumerate(keys)]
    close(app, again)
    assert codes_of(again) == ["txSUCCESS"] * 4


def _signers_changed_and_unchanged(app, monkeypatch):
    """Signer rows are written for the accounts whose signers a close
    changed and for no other: a SET_OPTIONS marks its account, a later
    payment's store of the same account or the fee charged on it does not
    clear the mark, and payments among accounts with signers write none."""
    from stellar_tpu.crypto.keys import PubKeyUtils

    def signer(i, weight):
        return T.set_options_op(signer=X.Signer(T.get_account("sg-signer-%d" % i).get_public_key(), weight))

    keys = [T.get_account("sg-%d" % i) for i in range(12)]
    first = funded(app, keys)
    flushes = []

    def traced_close(txs):
        app.tracer.clear()
        close(app, txs)
        assert codes_of(txs) == ["txSUCCESS"] * len(txs)
        (flush,) = [s.attrs for s in app.tracer.spans() if s.name == "commit.flush"]
        flushes.append((flush["signer_accounts"], flush["signer_rows"]))

    traced_close([T.tx_from_ops(app, k, first + 1, [signer(i, 1), signer(i + 100, 1)]) for i, k in enumerate(keys)])
    traced_close([pay(app, k, first + 2, keys[i ^ 1], 100) for i, k in enumerate(keys)])
    # the even accounts change a weight, then pay their partner; the odd
    # ones pay theirs
    traced_close(
        [T.tx_from_ops(app, k, first + 3, [signer(i, 3)]) for i, k in enumerate(keys) if not i & 1]
        + [pay(app, k, first + 4, keys[i ^ 1], 7) for i, k in enumerate(keys) if not i & 1]
        + [pay(app, k, first + 3, keys[i ^ 1], 9) for i, k in enumerate(keys) if i & 1]
    )
    assert flushes == [(12, 24), (0, 0), (6, 24)]
    assert app.invariants.total_violations == 0, app.invariants.dump_info()
    rows = sorted(app.database.query_all("SELECT accountid, publickey, weight FROM signers"))
    entries = sorted(
        (k.get_strkey_public(), PubKeyUtils.to_strkey(s.pubKey), s.weight)
        for k in keys
        for s in AccountFrame.load_account(k.get_public_key(), app.database).account.signers
    )
    assert rows == entries and len(rows) == 24
    assert sorted(w for _a, _p, w in rows) == [1] * 18 + [3] * 6


def _duplicate_source_seq_chain(app, monkeypatch):
    """Several transactions of one account in one set apply in sequence
    order, whatever order the set was handed over in, and every other
    account's between them by hash."""
    keys = [T.get_account("ch-%d" % i) for i in range(4)]
    first = funded(app, keys)
    txs = [pay(app, k, first + n, keys[(i + 1) % 4], 10 * n) for n in (3, 1, 2) for i, k in enumerate(keys[:3])]
    seq, order = close(app, txs)
    assert codes_of(txs) == ["txSUCCESS"] * 9
    at = history_of(app, seq)
    assert [at[tx.get_contents_hash().hex()][0] for tx in order] == list(range(1, 10))
    for k in keys[:3]:
        mine = sorted((tx for tx in txs if tx.get_source_id() == k.get_public_key()), key=lambda tx: tx.get_seq_num())
        indices = [at[tx.get_contents_hash().hex()][0] for tx in mine]
        assert indices == sorted(indices)
    held = accounts_of(app)
    assert held[keys[0].get_strkey_public()] == (START - 3 * FEE - 60, first + 3)
    assert held[keys[1].get_strkey_public()] == (START - 3 * FEE, first + 3)
    assert held[keys[3].get_strkey_public()] == (START + 60, first)


def _payments_counted(app, monkeypatch):
    """`apply.serial` carries `payments`: the PAYMENT operations of the set
    that went through credit / debit.  A payment that fails there counts (and
    the credit its destination was given is unwound: account row, cache line
    and the close's delta are the ones from before the close); a payment to
    oneself, a PATH_PAYMENT, a CREATE_ACCOUNT and a transaction refused
    before its operations do not.  `/info` shows the node's total."""
    from stellar_tpu.ledger.entryframe import entry_cache_of

    keys = [T.get_account("pc-%d" % i) for i in range(12)]
    first = funded(app, keys)
    native = X.Asset.native()
    lm = app.ledger_manager
    info = lambda: app.command_handler.handle_info({})["info"]["exchange"]  # noqa: E731
    before = info()
    assert before["payments_applied"] == 0  # funding is one CREATE_ACCOUNT a key

    def line_of(key):
        """(account row, decoded-entry cache line as XDR or None)"""
        frame = AccountFrame.load_account(key.get_public_key(), app.database)
        hit, entry = entry_cache_of(app.database).peek(frame.get_key().to_xdr())
        row = app.database.query_all(
            "SELECT * FROM accounts WHERE accountid=?", (key.get_strkey_public(),))
        return row, (entry.to_xdr() if hit and entry is not None else None)

    unpaid = keys[9]  # destination of the payment that fails, in no other transaction
    unpaid_before = line_of(unpaid)
    assert unpaid_before[1] is not None
    handed_to_buckets = []
    add_batch = app.bucket_manager.add_batch

    def recording(seq, live, dead):
        handed_to_buckets.extend(e.data.value.accountID for e in live)
        return add_batch(seq, live, dead)

    monkeypatch.setattr(app.bucket_manager, "add_batch", recording)

    txs = [pay(app, keys[i], first + 1, keys[i + 1], 10 + i) for i in (0, 2, 4)]
    txs += [
        pay(app, keys[6], first + 1, keys[6], 50),  # to oneself: returns before its body
        T.tx_from_ops(app, keys[7], first + 1, [T.path_payment_op(keys[1], native, 30, native, 30)]),
        T.tx_from_ops(app, keys[8], first + 1, [T.create_account_op(T.get_account("pc-new"), 3 * 10**8)]),
        pay(app, keys[10], first + 1, unpaid, 10**12),  # underfunded, after its destination was credited
        sign_with(pay(app, keys[11], first + 1, keys[0], 5), [keys[0]]),  # refused before its operations
    ]
    app.tracer.clear()
    close(app, txs)
    assert codes_of(txs) == ["txSUCCESS"] * 6 + ["txFAILED", "txBAD_AUTH"]
    assert T.inner_op_code(txs[6]).name == "PAYMENT_UNDERFUNDED"
    (serial,) = [s.attrs for s in app.tracer.spans() if s.name == "apply.serial"]
    assert serial == {"txs": 8, "accounts": 8, "failed": 2, "payments": 4}
    after = info()
    assert after["payments_applied"] - before["payments_applied"] == 4
    assert after["txs_failed_at_apply"] - before["txs_failed_at_apply"] == 2
    assert after["payments_applied"] == lm.exchange_stats["payments_applied"]
    # the destination that was credited and never paid
    assert line_of(unpaid) == unpaid_before
    assert unpaid.get_public_key() not in handed_to_buckets
    assert keys[10].get_public_key() in handed_to_buckets  # the fee its source paid
    held = accounts_of(app)
    assert held[unpaid.get_strkey_public()] == (START, first)
    assert held[keys[10].get_strkey_public()] == (START - FEE, first + 1)
    assert held[keys[6].get_strkey_public()] == (START - FEE, first + 1)
    assert held[keys[1].get_strkey_public()] == (START + 10 + 30, first)
    assert app.invariants.total_violations == 0, app.invariants.dump_info()
    # a close with no PAYMENT in it says so
    app.tracer.clear()
    close(app, [T.tx_from_ops(app, keys[7], first + 2, [T.path_payment_op(keys[1], native, 3, native, 3)])])
    (serial,) = [s.attrs for s in app.tracer.spans() if s.name == "apply.serial"]
    assert serial == {"txs": 1, "accounts": 1, "failed": 0, "payments": 0}


def _payment_builds_no_path_payment_frame(app, monkeypatch):
    """PAYMENT applies through the two halves it shares with PATH_PAYMENT,
    not through a PathPaymentOpFrame of its own making: with that frame's
    constructor (and `PathPaymentOp`'s) raising, payments that succeed, fail
    in either half or go to their own source still close as they should."""
    from stellar_tpu.tx import ops_payment

    keys = [T.get_account("np-%d" % i) for i in range(6)]
    first = funded(app, keys)

    def refuse(*a, **kw):
        raise AssertionError("a PAYMENT built a path payment")

    monkeypatch.setattr(ops_payment.PathPaymentOpFrame, "__init__", refuse)
    monkeypatch.setattr(X.PathPaymentOp, "__init__", refuse)
    txs = [
        pay(app, keys[0], first + 1, keys[1], 11),
        pay(app, keys[2], first + 1, keys[2], 12),
        pay(app, keys[3], first + 1, T.get_account("np-nobody"), 13),
        pay(app, keys[4], first + 1, keys[5], 10**12),
    ]
    close(app, txs)
    assert codes_of(txs) == ["txSUCCESS", "txSUCCESS", "txFAILED", "txFAILED"]
    assert [T.inner_op_code(tx).name for tx in txs[2:]] == ["PAYMENT_NO_DESTINATION", "PAYMENT_UNDERFUNDED"]
    held = accounts_of(app)
    assert held[keys[1].get_strkey_public()] == (START + 11, first)
    assert held[keys[5].get_strkey_public()] == (START, first)
    assert app.ledger_manager.exchange_stats["payments_applied"] == 3
    assert app.invariants.total_violations == 0, app.invariants.dump_info()


def _missing_source(app, monkeypatch):
    """A set that holds a transaction of an account that does not exist
    aborts in the fee pass, after the transactions ahead of it were charged:
    the node stays where it was, the entry cache is cleared, `txfeehistory`
    holds no row of the ledger, and the next, valid, set closes."""
    from stellar_tpu.ledger.entryframe import entry_cache_of

    keys = [T.get_account("ms-%d" % i) for i in range(4)]
    first = funded(app, keys)
    lm = app.ledger_manager
    nobody = pay(app, T.get_account("ms-nobody"), first + 1, keys[0], 5)
    txs = [pay(app, k, first + 1, keys[i ^ 1], 5) for i, k in enumerate(keys)] + [nobody]
    before = (lm.last_closed.hash, lm.last_closed.header.ledgerSeq, accounts_of(app))
    with pytest.raises(RuntimeError, match="missing source account"):
        close(app, txs)
    assert (lm.last_closed.hash, lm.last_closed.header.ledgerSeq, accounts_of(app)) == before
    assert not entry_cache_of(app.database)._map
    assert app.database.query_all("SELECT COUNT(*) FROM txfeehistory WHERE ledgerseq=?", (before[1] + 1,)) == [(0,)]
    good = [pay(app, k, first + 1, keys[i ^ 1], 5) for i, k in enumerate(keys)]
    close(app, good)
    assert codes_of(good) == ["txSUCCESS"] * 4
    assert accounts_of(app)[keys[0].get_strkey_public()] == (START - FEE, first + 1)


def _fee_of_one_equals_the_pass(app, monkeypatch):
    """`process_fee_seq_num` for one transaction leaves what the close's
    pass leaves for a set of that one: the delta's change, the header (its
    fee pool raised by the fee), the fee charged, the account every later
    load sees.  Over a fee the account can pay, one it cannot, and none."""
    from stellar_tpu.ledger.delta import LedgerDelta
    from stellar_tpu.xdr.ledger import LEDGER_ENTRY_CHANGES

    keys = [T.get_account("fo-%d" % i) for i in range(3)]
    first = funded(app, keys)
    lm = app.ledger_manager
    db = app.database

    class Undo(Exception):
        pass

    def left_by(charge, fee):
        tx = T.tx_from_ops(app, keys[0], first + 1, [T.payment_op(keys[1], 5)], fee=fee)
        delta = LedgerDelta(lm.current.header, db)
        pool = lm.current.header.feePool
        try:
            with db.transaction():
                charge(tx, delta)
                loaded = AccountFrame.load_account(keys[0].get_public_key(), db)
                left = (
                    LEDGER_ENTRY_CHANGES.pack(delta.get_changes()),
                    delta.header_ro().to_xdr(),
                    delta.header_ro().feePool - pool,
                    tx.result.feeCharged,
                    (loaded.get_balance(), loaded.get_seq_num()),
                )
                raise Undo
        except Undo:
            delta.rollback()
        assert lm.current.header.feePool == pool
        return left

    for fee, taken in ((100, 100), (START + 5, START), (0, 0)):
        alone = left_by(lambda tx, delta: tx.process_fee_seq_num(delta, lm), fee)
        as_a_set = left_by(lambda tx, delta: lm._process_fees_seq_nums([tx], delta), fee)
        assert alone == as_a_set
        assert alone[2:] == (taken, taken, (START - taken, first + 1))


OUTCOMES = {
    "failed-after-partners-payment": _failed_after_partners_payment,
    "bad-auth": _bad_auth,
    "bad-seq": _bad_seq,
    "missing-source-aborts-the-close": _missing_source,
    "fee-of-one-equals-the-pass-for-a-set-of-one": _fee_of_one_equals_the_pass,
    "underfunded": _underfunded,
    "op-raises": _op_raises,
    "unrollbackable-write": _unrollbackable_write,
    "signers-changed-and-unchanged-in-one-close": _signers_changed_and_unchanged,
    "duplicate-source-seq-chain": _duplicate_source_seq_chain,
    "payments-counted-on-the-span-and-in-info": _payments_counted,
    "payment-builds-no-path-payment-frame": _payment_builds_no_path_payment_frame,
}


@pytest.mark.parametrize("case", sorted(OUTCOMES))
def test_serial_apply_outcomes(case, monkeypatch):
    def paranoid(cfg):
        cfg.PARANOID_MODE = True

    app, clock = node(200, paranoid)
    try:
        OUTCOMES[case](app, monkeypatch)
    finally:
        app.graceful_stop()
        clock.shutdown()


# -- against plain arithmetic -------------------------------------------------

N = 24  # funded accounts


def _pairs(rng, r):
    return [(i, "pay", i ^ 1, rng.randrange(1, 5000)) for i in range(N)]


def _chain(rng, r):
    # each pays the next, more every close: in the third, more than it holds
    # unless the one before it was applied first
    return [(i, "pay", (i + 1) % N, (r + 1) * 35 * 10**7 - rng.randrange(1000)) for i in range(N)]


def _star(rng, r):
    hot = r % N
    return [(i, "pay", hot if i != hot else (hot + 1) % N, rng.randrange(1, 10**6)) for i in range(N)]


def _random_partners(rng, r):
    return [(i, "pay", rng.randrange(N), rng.randrange(1, 10**5)) for i in range(N)]


def _create_then_pay(rng, r):
    # account i creates a new one; its partner pays the new one in the same
    # close: found or not by the order the set applies in
    out = []
    for i in range(0, N, 2):
        new = "new-%d-%d" % (r, i)
        out.append((i, "create", new, 2 * 10**8 + rng.randrange(1000)))
        out.append((i + 1, "pay", new, rng.randrange(1, 1000)))
    return out


def _with_failures(rng, r):
    out = []
    for i in range(N):
        kind = rng.randrange(6)
        if kind == 0:
            out.append((i, "pay", rng.randrange(N), 10**12))  # more than it holds
        elif kind == 1:
            out.append((i, "pay", "nobody-%d-%d" % (r, i), 5))  # no such account
        elif kind == 2:
            out.append((i, "create", rng.randrange(N), 10**7))  # exists already
        elif kind == 3:
            out.append((i, "create", "poor-%d-%d" % (r, i), 3))  # under the reserve
        else:
            out.append((i, "pay", rng.randrange(N), rng.randrange(1, 10**5)))
    return out


SHAPES = {
    "pairs": _pairs,
    "chain": _chain,
    "star-hot-account": _star,
    "random-partners": _random_partners,
    "create-then-pay-same-close": _create_then_pay,
    "with-failures": _with_failures,
}


def _result_of(tx):
    code = tx.get_result_code().name
    if code not in ("txSUCCESS", "txFAILED"):
        return code, []
    return code, [T.inner_op_code(tx, i).name for i in range(len(tx.operations))]


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_closes_equal_the_reference_apply(shape, seed):
    """Funding, then three closes of the shape's traffic: after each, every
    account's balance and sequence number, the fee pool and every
    transaction's codes equal the reference's."""
    rng = random.Random(1000 * seed + sorted(SHAPES).index(shape))
    app, clock = node(202)
    try:
        lm = app.ledger_manager
        header = lm.last_closed.header
        ledger = Ledger(accounts_of(app), header.baseFee, header.baseReserve, header.feePool)
        by_name = {"root": T.root_key_for(app)}
        by_name.update({i: T.get_account("ra-%d-%d" % (seed, i)) for i in range(N)})

        def key(name):
            if name not in by_name:
                by_name[name] = T.get_account("ra-%d-%s" % (seed, name))
            return by_name[name]

        def strkey(name):
            return key(name).get_strkey_public()

        def run(plan):
            """plan: [(source, kind, destination, amount)] -> closed on both
            sides and compared; the reference's codes."""
            next_seq, pairs = {}, []
            for source, kind, dest, amount in plan:
                seq = next_seq.get(source, ledger.accounts[strkey(source)][1]) + 1
                next_seq[source] = seq
                op = (T.payment_op if kind == "pay" else T.create_account_op)(key(dest), amount)
                frame = T.tx_from_ops(app, key(source), seq, [op])
                pairs.append((frame, Tx(strkey(source), seq, frame.envelope.tx.fee, ((kind, strkey(dest), amount),))))
            ledger_seq, order = close(app, [f for f, _tx in pairs])
            model = {id(f): tx for f, tx in pairs}
            want = ledger.close(ledger_seq, [model[id(f)] for f in order])
            assert [_result_of(f) for f in order] == want
            assert accounts_of(app) == {name: tuple(state) for name, state in ledger.accounts.items()}
            assert lm.last_closed.header.feePool == ledger.fee_pool
            assert app.invariants.total_violations == 0, app.invariants.dump_info()
            return want

        run([("root", "create", i, START) for i in range(N)])
        seen = set()
        for r in range(3):
            for code, ops in run(SHAPES[shape](rng, r)):
                seen.add(code)
                seen.update(ops)
        # the shape sent what it is named for
        assert "PAYMENT_SUCCESS" in seen
        if shape == "with-failures":
            assert {"PAYMENT_UNDERFUNDED", "PAYMENT_NO_DESTINATION", "CREATE_ACCOUNT_ALREADY_EXIST",
                    "CREATE_ACCOUNT_LOW_RESERVE", "txFAILED"} <= seen
        if shape == "create-then-pay-same-close":
            assert {"CREATE_ACCOUNT_SUCCESS", "PAYMENT_NO_DESTINATION"} <= seen
        if shape == "chain":
            assert "PAYMENT_UNDERFUNDED" in seen
        # admission, alone: the next sequence number or none
        source = by_name[0]
        have = ledger.accounts[source.get_strkey_public()][1]
        for seq in (have, have + 1, have + 2):
            frame = pay(app, source, seq, by_name[1], 1)
            tx = Tx(source.get_strkey_public(), seq, frame.envelope.tx.fee, (("pay", strkey(1), 1),))
            frame.check_valid(app)
            assert frame.get_result_code().name == ledger.admit(tx)
    finally:
        app.graceful_stop()
        clock.shutdown()
