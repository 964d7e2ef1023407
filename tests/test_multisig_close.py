"""3-of-5 multisignature payment sets through the close (ISSUE 26,
``multisig5000``), at a small width on the CPU.

One ``SIGNATURE_BACKEND="tpu"`` node (the XLA lowering of the verify kernel
on the CPU, the cutover lowered and ``SIG_BATCH_MAX`` 16 so that a set's
flush takes several chunks of one bucket) and one plain ``cpu`` node are fed
the same sets.  Every verdict is held against the configuration's plain
reference, ``benchmarks/reference_multisig.py``: authorisation by plain
arithmetic over the signer table built here, libsodium through the
benchmark's own binding, result codes read from ``txhistory`` by sqlite3
alone.
"""

import hashlib
import random
import sqlite3

import pytest

import stellar_tpu.xdr as X
from benchmarks import reference_multisig as RM
from stellar_tpu.crypto.keys import PubKeyUtils, SecretKey
from stellar_tpu.herder.ledgerclose import LedgerCloseData
from stellar_tpu.herder.txset import TxSetFrame
from stellar_tpu.ledger.accountframe import AccountFrame
from stellar_tpu.main.application import Application
from stellar_tpu.tx import testutils as T
from stellar_tpu.tx.frame import TransactionFrame
from stellar_tpu.util import VIRTUAL_TIME, VirtualClock
from stellar_tpu.xdr.ledger import StellarValue

WIDTH = 16  # transactions a set: 48 signatures, three chunks of 16 lanes
SET_ACCOUNTS = 2 * WIDTH  # sources and destinations of the payment sets
PER, SIGN, THRESHOLD = 5, 3, 3
BALANCE = 10**9
# two keys whose public keys end in the same four bytes, found once by a
# birthday search over ``hint_key(i)`` (the first pair, after 27,645 keys)
HINT_PAIR = (5018, 27644)

RC = X.TransactionResultCode


def key(label: str, i: int) -> SecretKey:
    return SecretKey.from_seed(hashlib.sha256(b"multisig close %s %d" % (label.encode(), i)).digest())


def hint_key(i: int) -> SecretKey:
    return SecretKey.from_seed(hashlib.sha256(b"stellar-tpu hint pair %d" % i).digest())


def signed_by(app, source, seq, ops, signers) -> TransactionFrame:
    frame = T.tx_from_ops(app, source, seq, ops)
    frame.envelope.signatures = []
    frame.clear_cached()
    for s in signers:
        frame.add_signature(s)
    return frame


class Node:
    def __init__(self, instance: int, backend: str, db_path: str):
        cfg = T.get_test_config(instance, backend=backend)
        cfg.HTTP_PORT = 0
        cfg.DATABASE = f"sqlite3://{db_path}"
        cfg.TPU_CPU_CUTOVER = 8
        cfg.SIG_BATCH_MAX = 16
        self.db_path = db_path
        self.clock = VirtualClock(VIRTUAL_TIME)
        self.app = Application.create(self.clock, cfg, new_db=True)
        self.lm = self.app.ledger_manager

    def frames(self, blobs):
        nid = self.app.network_id
        return [TransactionFrame(nid, X.TransactionEnvelope.from_xdr(b)) for b in blobs]

    def ledger_data(self, blobs) -> LedgerCloseData:
        txset = TxSetFrame(self.lm.last_closed.hash, self.frames(blobs))
        txset.sort_for_hash()
        value = StellarValue(
            txset.get_contents_hash(), self.lm.last_closed.header.scpValue.closeTime + 5, [], 0
        )
        return LedgerCloseData(self.lm.current.header.ledgerSeq, txset, value)

    def close(self, blobs, validate: bool = True) -> bytes:
        """Validate and externalize a set, as the benchmark's close cells
        do; ``validate=False`` forces it past ``check_valid``."""
        ledger_data = self.ledger_data(blobs)
        if validate:
            assert ledger_data.tx_set.check_valid(self.app)
        self.lm.externalize_value(ledger_data)
        return self.lm.last_closed.hash

    def stop(self):
        self.app.graceful_stop()
        self.clock.shutdown()


class World:
    """Both nodes, the accounts and their signers, and the sets fed so far."""

    def __init__(self, tmp):
        self.tpu = Node(96, "tpu", str(tmp / "tpu.db"))
        self.cpu = Node(97, "cpu", str(tmp / "cpu.db"))
        app = self.tpu.app
        n = SET_ACCOUNTS + len(EDGES)
        self.keys = [key("acct", i) for i in range(n)]
        self.signers = [[key("signer", i * PER + j) for j in range(PER)] for i in range(n)]
        # the last account (the last edge's) holds the two keys that share a hint
        self.signers[-1][:2] = [hint_key(i) for i in HINT_PAIR]
        self.held = {
            k.public_raw: RM.Account(tuple((s.public_raw, 1) for s in mine), 0, THRESHOLD)
            for k, mine in zip(self.keys, self.signers)
        }
        root = T.root_key_for(app)
        fund = T.tx_from_ops(app, root, 1, [T.create_account_op(k, BALANCE) for k in self.keys])
        self.feed([fund.envelope.to_xdr()])
        self.seq = {i: (self.tpu.lm.last_closed.header.ledgerSeq << 32) + 1 for i in range(n)}
        install = []
        for i, (k, mine) in enumerate(zip(self.keys, self.signers)):
            ops = [
                T.set_options_op(signer=X.Signer(s.get_public_key(), 1))
                for s in mine[:-1]
            ] + [
                T.set_options_op(
                    master_weight=0, low=THRESHOLD, med=THRESHOLD, high=THRESHOLD,
                    signer=X.Signer(mine[-1].get_public_key(), 1),
                )
            ]
            install.append(T.tx_from_ops(app, k, self.next_seq(i), ops).envelope.to_xdr())
        app.tracer.clear()
        self.feed(install)
        spans, _, _ = app.tracer.snapshot(clear=True)
        # what the close that installs the signers wrote (the span test)
        (self.install_flush,) = [s.attrs for s in spans if s.name == "commit.flush"]
        self.rounds = 0

    def next_seq(self, i: int) -> int:
        seq = self.seq[i]
        self.seq[i] = seq + 1
        return seq

    def payment(self, s: int, d: int, signers) -> bytes:
        return signed_by(
            self.tpu.app, self.keys[s], self.next_seq(s), [T.payment_op(self.keys[d], 1000)], signers
        ).envelope.to_xdr()

    def payment_set(self) -> list:
        """WIDTH payments between distinct accounts, each signed by a
        seeded 3 of its source's 5 signers in seeded order."""
        rng = random.Random(2600 + self.rounds)
        self.rounds += 1
        order = list(range(SET_ACCOUNTS))
        rng.shuffle(order)
        return [
            self.payment(s, d, rng.sample(self.signers[s], SIGN))
            for s, d in zip(order[:WIDTH], order[WIDTH:])
        ]

    def feed(self, blobs, validate: bool = True) -> None:
        """The same set to both nodes, the device node first (the verify
        cache is process-wide: what it latched must not answer for the
        plain node); the ledger hashes must agree."""
        h = self.tpu.close(blobs, validate)
        PubKeyUtils.clear_verify_sig_cache()
        assert self.cpu.close(blobs, validate) == h
        PubKeyUtils.clear_verify_sig_cache()

    def expected(self, blobs) -> list:
        envs = [X.TransactionEnvelope.from_xdr(b) for b in blobs]
        return RM.expected_codes(envs, self.held, self.tpu.app.network_id)

    def stop(self):
        self.tpu.stop()
        self.cpu.stop()


def _edge_signers(name: str, mine: list, master, stranger) -> list:
    return {
        "three-of-five": mine[:3],
        "two-of-five": mine[:2],
        "four-where-three-suffice": mine[:4],
        "a-non-signer": [mine[0], stranger, mine[1]],
        "one-signer-twice": [mine[0], mine[0], mine[1]],
        "master-at-weight-0": [master, mine[0], mine[1]],
        "three-in-another-order": [mine[4], mine[0], mine[2]],
        "no-signature": [],
    }[name]


EDGES = {
    "three-of-five": RC.txSUCCESS,
    "two-of-five": RC.txBAD_AUTH,
    "four-where-three-suffice": RC.txBAD_AUTH_EXTRA,
    "a-non-signer": RC.txBAD_AUTH,
    "one-signer-twice": RC.txBAD_AUTH,
    "master-at-weight-0": RC.txBAD_AUTH,
    "three-in-another-order": RC.txSUCCESS,
    "no-signature": RC.txBAD_AUTH,
    # the signature of the second key of the pair: two candidate triples,
    # of which one verifies
    "shared-hint": RC.txSUCCESS,
}


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    w = World(tmp_path_factory.mktemp("multisig"))
    yield w
    w.stop()


def stats(node) -> dict:
    return node.app.sig_backend.stats()


def test_sets_close_as_on_a_plain_node_and_as_the_plain_reference_says(world):
    """(a) hashes equal the cpu node's (``feed``); every result code is the
    plain reference's and the cpu node's; each flush took three chunks."""
    fed = []
    for _ in range(3):
        before = stats(world.tpu)
        blobs = world.payment_set()
        world.feed(blobs)
        after = stats(world.tpu)
        assert after["items"] - before["items"] == SIGN * WIDTH
        assert after["device_calls"] - before["device_calls"] == SIGN * WIDTH // 16
        assert after["cpu_cutover_items"] == before["cpu_cutover_items"]
        fed.extend(blobs)
    want = world.expected(fed)
    assert [code for _, code in want] == [RM.TX_SUCCESS] * len(fed)
    for node in (world.tpu, world.cpu):
        stored = RM.result_codes(node.db_path)
        assert RM.authorisation_differs(want, stored) == 0
    rows = {
        strkey(k): {strkey(pk): w for pk, w in a.signers} for k, a in world.held.items()
    }
    assert RM.signer_rows_off(world.tpu.db_path, rows) == 0
    assert RM.signer_rows_off(world.cpu.db_path, rows) == 0


def strkey(raw: bytes) -> str:
    return PubKeyUtils.to_strkey(X.PublicKey.from_ed25519(raw))


def test_the_prefetch_is_complete_and_the_spans_say_what_the_set_implies(world):
    """(c) across a validated and closed set ``triples`` = 3 x ``txs`` and
    no signature is left to the eager verify; (d) ``sig.collect``, the
    sampled ``tx.valid`` and ``commit.flush`` carry the set's numbers."""
    node = world.tpu
    blobs = world.payment_set()
    node.app.tracer.clear()
    before = stats(node)
    node.close(blobs)
    after = stats(node)
    spans, _, dropped = node.app.tracer.snapshot(clear=True)
    PubKeyUtils.clear_verify_sig_cache()
    world.cpu.close(blobs)
    PubKeyUtils.clear_verify_sig_cache()
    assert not dropped
    assert after["eager_host_verifies"] == before["eager_host_verifies"]
    assert after["items"] - before["items"] == SIGN * WIDTH

    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    (collect,) = by_name["sig.collect"]  # once: the close's prewarm hits the memo
    assert collect.attrs == {
        "txs": WIDTH, "signatures": SIGN * WIDTH, "triples": SIGN * WIDTH, "accounts": WIDTH,
    }
    (validate,) = by_name["txset.validate"]
    assert collect.parent == validate.sid
    (valid,) = by_name["tx.valid"]  # one transaction in 64: index 0
    assert valid.attrs == {"sigs": SIGN, "keys": PER}
    (flush,) = by_name["commit.flush"]
    # 32 accounts touched and no signer of theirs changed: no row of
    # ``signers`` written, and the span says 0 rather than leave the key out
    assert flush.attrs == {"account_rows": 2 * WIDTH, "rowids_taken": 0, "signer_rows": 0, "signer_accounts": 0}
    # the close that installed them: five rows inserted an account, none
    # there to delete
    n = len(world.keys)
    assert world.install_flush == {"account_rows": n, "rowids_taken": 0, "signer_rows": n * PER, "signer_accounts": n}


@pytest.fixture(scope="module")
def edges(world):
    """Every edge envelope, each from an account of its own: what
    ``check_valid`` says of it alone and of a set that holds it, then all
    of them forced past ``check_valid`` into one closed ledger."""
    app = world.tpu.app
    stranger = key("stranger", 0)
    blobs, alone = {}, {}
    for n, name in enumerate(EDGES):
        i = SET_ACCOUNTS + n
        mine = world.signers[i]
        if name == "shared-hint":
            # sign with the key of the pair that the account lists second:
            # the walk then tries the first, under which it does not verify
            listed = AccountFrame.load_account(
                world.keys[i].get_public_key(), app.database, readonly=True
            ).account.signers
            second = [s.pubKey.value for s in listed if s.pubKey.value[-4:] == mine[0].public_raw[-4:]][1]
            signers = [k for k in mine[:2] if k.public_raw == second] + mine[2:4]
        else:
            signers = _edge_signers(name, mine, world.keys[i], stranger)
        blobs[name] = world.payment(i, 0, signers)
    for name, blob in blobs.items():
        eager = stats(world.tpu)["eager_host_verifies"]
        in_a_set = world.tpu.ledger_data([blob]).tx_set.check_valid(app)
        eager = stats(world.tpu)["eager_host_verifies"] - eager
        (frame,) = world.tpu.frames([blob])
        alone[name] = {
            "set": in_a_set, "eager": eager, "ok": frame.check_valid(app), "code": frame.get_result_code(),
            "triples": len(frame.candidate_signature_pairs(app.database)),
            "signatures": len(frame.envelope.signatures),
        }
        PubKeyUtils.clear_verify_sig_cache()
    world.feed(list(blobs.values()), validate=False)
    want = dict(zip(blobs, world.expected(list(blobs.values()))))
    return {
        "alone": alone, "want": want,
        "tpu": RM.result_codes(world.tpu.db_path), "cpu": RM.result_codes(world.cpu.db_path),
    }


@pytest.mark.parametrize("name", list(EDGES))
def test_an_edge_envelope_gets_the_plain_references_verdict(edges, name):
    """(b) alone, in a set and applied, on both nodes."""
    txid, code = edges["want"][name]
    assert code == EDGES[name].value  # the plain reference, against the matrix written here
    alone = edges["alone"][name]
    assert alone["code"].value == code
    assert alone["ok"] is (code == RM.TX_SUCCESS)
    assert alone["set"] is (code == RM.TX_SUCCESS)  # a set that holds it is refused
    assert edges["tpu"][txid] == code and edges["cpu"][txid] == code
    if name == "shared-hint":
        # one signature of the three matches two keys: four candidates,
        # and the one that does not verify is never latched, so the
        # set's validation verifies it again, eagerly
        assert alone["triples"] == alone["signatures"] + 1
        assert alone["eager"] >= 1
    else:
        # the prefetch left nothing to the eager verify; a stranger's and
        # the weightless master's signatures match no key at all
        assert alone["eager"] == 0
        matched = {"a-non-signer": 2, "master-at-weight-0": 2}.get(name, alone["signatures"])
        assert alone["triples"] == matched


def test_the_hint_pair_is_one():
    a, b = (hint_key(i).public_raw for i in HINT_PAIR)
    assert a != b and a[-4:] == b[-4:]


def test_a_closed_envelope_without_three_signers_counts_against_the_guarantee(edges):
    """The row the benchmark's check adds: of the nine edge envelopes forced
    into a ledger, the six that three distinct signers did not sign (or
    that carry a signature too many) each count, on either node."""
    want = list(edges["want"].values())
    bad = sum(1 for code in EDGES.values() if code != RC.txSUCCESS)
    assert RM.authorisation_differs(want, edges["tpu"]) == bad
    assert RM.authorisation_differs(want, edges["cpu"]) == bad
    # and a node whose stored code differed would count too
    txid, _ = edges["want"]["three-of-five"]
    assert RM.authorisation_differs(want, dict(edges["tpu"], **{txid: RM.TX_BAD_AUTH})) == bad + 1


def test_the_signers_table_is_read_without_the_program(world):
    con = sqlite3.connect(f"file:{world.tpu.db_path}?mode=ro", uri=True)
    try:
        per_account = con.execute("SELECT COUNT(*), MIN(weight), MAX(weight) FROM signers GROUP BY accountid").fetchall()
    finally:
        con.close()
    assert len(per_account) == len(world.keys)
    assert set(per_account) == {(PER, 1, 1)}
