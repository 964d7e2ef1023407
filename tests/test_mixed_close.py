"""LoadGenerator's operation mix through the close (ISSUE 30, ``mixed1000``),
at small sizes on the CPU: the node against the plain ledger
(``tests/reference_apply.py``), result codes and rows.

- ``test_operation_outcomes``: one case an outcome of the exchange, of
  trustlines, of reserves and of signers; every close is compared, codes
  (the frames' and those read back from ``txhistory`` by the benchmark's
  reader) and every row of ``accounts``, ``trustlines``, ``offers`` and
  ``signers`` (by ``sqlite3`` alone).
- ``test_store_buffer``: what the write-back buffer has to get right when
  offers are made, crossed, deleted and rolled back inside one close.
- ``test_seeded_mixed_closes``: the benchmark's planner at three shapes and
  two seeds, three closes each, on a ``cpu`` node and on a ``tpu``-backend
  node over XLA/CPU at cutover 8: equal to each other, to the plain ledger,
  and to the benchmark's copy of it (``benchmarks/reference_mixed.py``), so
  the copy cannot drift.
- the new span attributes and counters.
"""

import copy
import hashlib
import random

import pytest
from reference_apply import Ledger, Tx

from benchmarks import reference_mixed as RM
from benchmarks.generators import mixed_closes as MC
from stellar_tpu.crypto.keys import PubKeyUtils, SecretKey
from stellar_tpu.herder.ledgerclose import LedgerCloseData
from stellar_tpu.herder.txset import TxSetFrame
from stellar_tpu.main.application import Application
from stellar_tpu.tx import testutils as T
from stellar_tpu.util import VIRTUAL_TIME, VirtualClock
from stellar_tpu.xdr.ledger import StellarValue

START = 10**10  # what a funded account holds
HELD = 10**6  # what a holder is paid of a credit
BIG = 10**9  # a trustline's limit
RESERVE = 10**8


def secret(label: str) -> SecretKey:
    return SecretKey.from_seed(hashlib.sha256(b"mixed close " + label.encode()).digest())


def result_of(frame):
    code = frame.get_result_code().name
    if code not in ("txSUCCESS", "txFAILED"):
        return code, []
    ops = []
    for r in frame.result.result.value:
        ops.append(r.value.value.type.name if r.type.name == "opINNER" else r.type.name)
    return code, ops


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

    def close(self, frames, validate=True):
        """-> (ledger sequence, the frames in apply order)."""
        txset = TxSetFrame(self.lm.last_closed.hash, list(frames))
        txset.sort_for_hash()
        order = txset.sort_for_apply()
        value = StellarValue(txset.get_contents_hash(), self.lm.last_closed.header.scpValue.closeTime + 5, [], 0)
        data = LedgerCloseData(self.lm.current.header.ledgerSeq, txset, value)
        if validate:
            assert txset.check_valid(self.app)
            self.lm.externalize_value(data)
        else:
            self.lm.close_ledger(data)
        return self.lm.last_closed.header.ledgerSeq, order

    def stop(self):
        self.app.graceful_stop()
        self.clock.shutdown()


class World:
    """Nodes fed the same sets, and the plain ledger beside them."""

    def __init__(self, tmp, instance: int, backends=("cpu",)):
        self.nodes = [Node(instance + i, b, str(tmp / f"{b}{i}.db")) for i, b in enumerate(backends)]
        app = self.nodes[0].app
        self.network_id = app.network_id
        header = app.ledger_manager.last_closed.header
        root = T.root_key_for(app)
        self.secrets = {root.get_strkey_public(): root}
        self.root = root.get_strkey_public()
        self.fee = header.baseFee
        genesis = {self.root: [header.totalCoins, 0]}
        self.plain = Ledger(genesis, header.baseFee, header.baseReserve)
        self.copy = RM.Ledger(genesis, header.baseFee, header.baseReserve)  # the benchmark's
        self.seen = set()

    def n(self, label: str) -> str:
        """The account (or key) of that label."""
        key = secret(label)
        self.secrets[key.get_strkey_public()] = key
        return key.get_strkey_public()

    def asset(self, code: str, issuer: str):
        return (code, self.n(issuer))

    def tx(self, source: str, ops, signed_by=None, pending=None) -> Tx:
        source = source if source in self.secrets else self.n(source)
        seq = self.plain.accounts[source][1] + 1 + (pending or {}).get(source, 0)
        if pending is not None:
            pending[source] = pending.get(source, 0) + 1
        return Tx(source, seq, self.fee * len(ops), tuple(ops), signed_by)

    def close(self, plan, validate=True, before=None):
        """Close ``plan`` — [(source, ops[, signed_by])] or ready ``Tx`` —
        on every node and on the plain ledgers, and compare everything.
        ``before(frames) -> bool`` may refuse the set's apply order (the
        caller then varies the plan).  -> [(tx, (code, op codes))]."""
        pending = {}
        txs = [p if isinstance(p, Tx) else self.tx(*p, pending=pending) for p in plan]
        want = None
        for node in self.nodes:
            pairs = [(MC.frame_of(self.network_id, tx, self.secrets), tx) for tx in txs]
            plain_of = {id(f): tx for f, tx in pairs}
            if before is not None and node is self.nodes[0]:
                txset = TxSetFrame(node.lm.last_closed.hash, [f for f, _ in pairs])
                txset.sort_for_hash()
                if not before([plain_of[id(f)] for f in txset.sort_for_apply()]):
                    return None
            # the verify cache is process-wide: what one node verified must
            # not answer for the next
            PubKeyUtils.clear_verify_sig_cache()
            seq, order = node.close([f for f, _ in pairs], validate)
            if want is None:
                applied = [plain_of[id(f)] for f in order]
                want = self.plain.close(seq, applied)
                assert self.copy.close(seq, applied) == want
                assert RM.state_of(self.copy) == RM.state_of(self.plain)
            assert [result_of(f) for f in order] == want, node.db_path
            stored = {txid: codes for txid, codes in RM.stored_history(node.db_path)[seq]}
            for f, codes in zip(order, want):
                have = stored[f.get_contents_hash().hex()]
                # the reader stops after an operation whose result carries claimed offers
                assert have[0] == codes[0] and have[1] == codes[1][: len(have[1])]
                assert len(have[1]) == len(codes[1]) or have[1][-1].startswith(("PATH_PAYMENT", "MANAGE_OFFER"))
            expect, found = RM.state_of(self.plain), RM.stored_state(node.db_path)
            for table in ("accounts", "trustlines", "offers", "signers"):
                assert found[table] == expect[table], (table, node.db_path)
            assert found["duplicate_signer_rows"] == 0
            assert node.lm.last_closed.header.feePool == self.plain.fee_pool
            assert node.lm.last_closed.header.idPool == self.plain.id_pool
            assert node.app.invariants.total_violations == 0, node.app.invariants.dump_info()
        assert len({n.lm.last_closed.hash for n in self.nodes}) == 1
        for _tx, (code, ops) in zip(applied, want):
            self.seen.add(code)
            self.seen.update(ops)
        return list(zip(applied, want))

    def fund(self, *labels, balance=START):
        self.close([(self.root, [("create", self.n(label), balance) for label in labels])])

    def stop(self):
        for node in self.nodes:
            node.stop()


@pytest.fixture
def world(tmp_path):
    w = World(tmp_path, 210)
    yield w
    w.stop()


def market(w):
    """Issuer I's USD held by A, B, C and D (a million each); E funded and
    trusting nothing."""
    usd = w.asset("USD", "I")
    w.fund("I", "J", "A", "B", "C", "D", "E")
    w.close([(x, [("trust", usd, BIG)]) for x in "ABCD"])
    w.close([("I", [("pay", w.n(x), HELD, usd) for x in "ABCD"])])
    return usd


def asks(w, usd, *offers):
    """Rest asks (seller, amount, price numerator over 100), one close each
    so that their ids follow the order given."""
    for seller, amount, n in offers:
        w.close([(seller, [("offer", usd, None, amount, (n, 100), 0)])])


# -- per-operation outcomes ---------------------------------------------------------------------------


def case_line_full(w):
    usd = market(w)
    w.close([("D", [("trust", usd, HELD + 50)])])
    w.close([("A", [("pay", w.n("D"), 51, usd)]), ("B", [("pay", w.n("C"), 51, usd)])])
    return {"PAYMENT_LINE_FULL", "PAYMENT_SUCCESS"}


def case_no_trust(w):
    usd = market(w)
    w.close([("A", [("pay", w.n("E"), 10, usd)]), ("E", [("pay", w.n("A"), 10, usd)])])
    return {"PAYMENT_NO_TRUST", "PAYMENT_SRC_NO_TRUST"}


def case_not_authorised(w):
    usd = market(w)
    eur = w.asset("EUR", "J")
    w.close([("J", [("options", (("setFlags", 1),))])])
    w.close([("A", [("trust", eur, BIG)]), ("B", [("trust", eur, BIG)])])
    assert w.plain.trustlines[(w.n("A"), eur)] == [0, BIG, False]
    w.close([("J", [("pay", w.n("A"), 10, eur)])])
    # a line that is not authorised can neither sell nor buy
    w.close([("A", [("offer", None, eur, 100, (1, 1), 0)]), ("B", [("offer", eur, None, 100, (1, 1), 0)])])
    return {"PAYMENT_NOT_AUTHORIZED", "MANAGE_OFFER_BUY_NOT_AUTHORIZED", "MANAGE_OFFER_UNDERFUNDED"}


def case_underfunded_by_reserve_with_sub_entries(w):
    usd = market(w)
    eur = w.asset("EUR", "J")
    # P and Q hold the same; P carries two sub-entries, so its reserve is two
    # base reserves higher and the same payment leaves it short
    w.fund("P", "Q", balance=4 * RESERVE + 1000)
    w.close([("P", [("trust", usd, BIG), ("trust", eur, BIG)])])
    w.close([("P", [("pay", w.n("A"), RESERVE, None)]), ("Q", [("pay", w.n("A"), RESERVE, None)])])
    # and a third sub-entry is refused where the balance is under five reserves
    w.close([("P", [("options", (("signer", (w.n("S"), 1)),))]), ("P", [("offer", None, usd, 10, (1, 1), 0)]),
             ("P", [("trust", w.asset("GBP", "J"), BIG)])])
    return {"PAYMENT_UNDERFUNDED", "PAYMENT_SUCCESS", "SET_OPTIONS_LOW_RESERVE", "MANAGE_OFFER_LOW_RESERVE",
            "CHANGE_TRUST_LOW_RESERVE"}


def case_offer_taken_whole(w):
    usd = market(w)
    asks(w, usd, ("B", 1000, 101))
    w.close([("A", [("path", w.n("D"), None, 2000, usd, 1000, ())])])
    assert not w.plain.offers and w.plain.subentries[w.n("B")] == 1
    return {"PATH_PAYMENT_SUCCESS"}


def case_offer_taken_in_part(w):
    usd = market(w)
    asks(w, usd, ("B", 1000, 101))
    w.close([("A", [("path", w.n("D"), None, 2000, usd, 400, ())])])
    assert [o[3] for o in w.plain.offers.values()] == [600]
    return {"PATH_PAYMENT_SUCCESS"}


def case_offers_across_a_page_of_five(w):
    usd = market(w)
    # seven asks: by price, then by offer id — the cheapest is the last made
    asks(w, usd, ("B", 100, 102), ("C", 100, 102), ("B", 100, 102), ("C", 100, 102), ("B", 100, 102),
         ("C", 1000, 103), ("B", 100, 101))
    w.close([("A", [("path", w.n("D"), None, 2000, usd, 700, ())])])
    assert {i: o[3] for i, o in w.plain.offers.items()} == {6: 900}
    assert w.plain.accounts[w.n("A")][0] == START - 2 * w.fee - (101 + 5 * 102 + 103)
    return {"PATH_PAYMENT_SUCCESS"}


def case_an_amount_the_price_does_not_divide(w):
    usd = market(w)
    asks(w, usd, ("B", 1000, 101), ("C", 1000, 101))
    # 10 wheat at 101/100: 10 sheep, then 9 wheat towards the seller
    w.close([("A", [("path", w.n("D"), None, 2000, usd, 10, ())])])
    w.close([("A", [("path", w.n("D"), None, 2000, usd, 100, ())])])
    # an arriving offer of 500 native buys 495 and is used up: nothing rests
    w.close([("D", [("offer", None, usd, 500, (99, 100), 0)])])
    assert sorted(o[3] for o in w.plain.offers.values()) == [405, 1000]
    # what is left of the first ask is now taken whole only at a loss of one: a
    # payment that needs it and more ends on the partial fill
    w.close([("A", [("path", w.n("D"), None, 2000, usd, 500, ())])])
    return {"PATH_PAYMENT_TOO_FEW_OFFERS", "PATH_PAYMENT_SUCCESS", "MANAGE_OFFER_SUCCESS"}


def case_cross_self(w):
    usd = market(w)
    asks(w, usd, ("B", 1000, 101), ("A", 1000, 102))
    w.close([("A", [("offer", None, usd, 5000, (97, 100), 0)])])
    w.close([("A", [("path", w.n("D"), None, 9000, usd, 1500, ())])])
    # within the first ask alone its own offer is never met
    w.close([("A", [("path", w.n("D"), None, 9000, usd, 500, ())])])
    # and an offer priced short of its own does not cross it
    w.close([("A", [("offer", None, usd, 101, (99, 100), 0)])])
    assert sorted(o[3] for o in w.plain.offers.values()) == [400, 1000]
    return {"MANAGE_OFFER_CROSS_SELF", "PATH_PAYMENT_OFFER_CROSS_SELF", "PATH_PAYMENT_SUCCESS", "MANAGE_OFFER_SUCCESS"}


def case_too_few_offers(w):
    usd = market(w)
    asks(w, usd, ("B", 1000, 101))
    w.close([("A", [("path", w.n("D"), None, 9000, usd, 1100, ())])])
    w.close([("A", [("path", w.n("D"), None, 9000, w.asset("EUR", "J"), 10, ())])])
    return {"PATH_PAYMENT_TOO_FEW_OFFERS", "PATH_PAYMENT_NO_TRUST"}


def case_over_sendmax(w):
    usd = market(w)
    asks(w, usd, ("B", 1000, 101))
    w.close([("A", [("path", w.n("D"), None, 100, usd, 100, ())]), ("C", [("path", w.n("D"), None, 101, usd, 100, ())])])
    return {"PATH_PAYMENT_OVER_SENDMAX", "PATH_PAYMENT_SUCCESS"}


def case_a_two_conversion_path(w):
    usd = market(w)
    eur = w.asset("EUR", "J")
    w.close([(x, [("trust", eur, BIG)]) for x in "BCD"])
    w.close([("J", [("pay", w.n(x), HELD, eur) for x in "BC"])])
    # C buys USD with native (a bid), B sells EUR for native (an ask): A's USD
    # goes through native to D's EUR
    w.close([("C", [("offer", None, usd, 20000, (102, 100), 0)])])
    asks(w, eur, ("B", 20000, 101))
    w.close([("A", [("path", w.n("D"), usd, 20000, eur, 10000, (None,))])])
    assert w.plain.trustlines[(w.n("D"), eur)][0] == 10000
    assert w.plain.trustlines[(w.n("A"), usd)][0] == HELD - 101 * 102
    return {"PATH_PAYMENT_SUCCESS"}


def case_offer_update_and_delete_by_id(w):
    usd = market(w)
    asks(w, usd, ("B", 1000, 101), ("C", 1000, 102))
    w.close([("B", [("offer", usd, None, 700, (103, 100), 1)]), ("C", [("offer", usd, None, 0, (102, 100), 2)]),
             ("A", [("offer", usd, None, 500, (101, 100), 1)])])  # another account's id
    assert w.plain.offers == {1: (w.n("B"), usd, None, 700, 103, 100)}
    assert w.plain.subentries[w.n("C")] == 1
    # an update that re-prices the offer so that it crosses what rests on the other side
    w.close([("C", [("offer", None, usd, 5000, (101, 100), 0)])])
    w.close([("B", [("offer", usd, None, 2000, (99, 100), 1)])])
    return {"MANAGE_OFFER_SUCCESS", "MANAGE_OFFER_NOT_FOUND"}


def case_trust_limit_under_the_balance(w):
    usd = market(w)
    w.close([("A", [("trust", usd, HELD - 1)]), ("B", [("trust", usd, HELD)])])
    assert w.plain.trustlines[(w.n("B"), usd)] == [HELD, HELD, True]
    return {"CHANGE_TRUST_INVALID_LIMIT", "CHANGE_TRUST_SUCCESS"}


def case_deleting_a_line_that_holds_credit(w):
    usd = market(w)
    w.close([("A", [("trust", usd, 0)])])
    w.close([("A", [("pay", w.n("I"), HELD, usd)])])  # back to the issuer, who needs no line
    w.close([("A", [("trust", usd, 0)]), ("E", [("trust", usd, 0)]), ("B", [("trust", w.asset("X", "nobody"), 5)])])
    assert (w.n("A"), usd) not in w.plain.trustlines and w.plain.subentries[w.n("A")] == 0
    return {"CHANGE_TRUST_INVALID_LIMIT", "CHANGE_TRUST_SUCCESS", "CHANGE_TRUST_NO_ISSUER"}


def case_create_account_that_exists(w):
    market(w)
    w.close([("A", [("create", w.n("B"), 3 * RESERVE)]), ("B", [("create", w.n("new"), 3 * RESERVE)]),
             ("C", [("create", w.n("poor"), RESERVE)])])
    return {"CREATE_ACCOUNT_ALREADY_EXIST", "CREATE_ACCOUNT_SUCCESS", "CREATE_ACCOUNT_LOW_RESERVE"}


def case_a_signer_added_signing_and_removed(w):
    market(w)
    a, s = w.n("A"), w.n("S")
    w.close([("A", [("options", (("signer", (s, 1)),))])])
    assert w.plain.signers[a] == {s: 1} and w.plain.subentries[a] == 2
    # the next close: the signer signs alone, and so does the master key
    w.close([("A", [("pay", w.n("B"), 5, None)], (s,)), ("A", [("pay", w.n("B"), 6, None)], (a,))])
    # both keys where one is enough: the second is never needed
    w.close([("A", [("pay", w.n("B"), 7, None)], (s, a))], validate=False)
    # the third: the signer removes itself
    w.close([("A", [("options", (("signer", (s, 0)),))], (s,))])
    assert not w.plain.signers[a] and w.plain.subentries[a] == 1
    # and then signs for nothing (a set holding it is not valid: forced past check_valid)
    w.close([("A", [("pay", w.n("B"), 8, None)], (s,))], validate=False)
    return {"SET_OPTIONS_SUCCESS", "PAYMENT_SUCCESS", "txBAD_AUTH_EXTRA", "txBAD_AUTH"}


def case_a_seller_that_cannot_deliver(w):
    usd = market(w)
    asks(w, usd, ("B", 1000, 101), ("C", 1000, 102))
    # B pays its dollars away: its ask shrinks to what it still holds
    w.close([("B", [("pay", w.n("C"), HELD - 300, usd)])])
    w.close([("A", [("path", w.n("D"), None, 9000, usd, 500, ())])])
    assert {i: o[3] for i, o in w.plain.offers.items()} == {2: 800}
    return {"PATH_PAYMENT_SUCCESS"}


def case_an_offer_with_a_full_line(w):
    usd = market(w)
    w.close([("D", [("trust", usd, HELD)])])
    w.close([("D", [("offer", None, usd, 100, (1, 1), 0)]), ("E", [("offer", None, usd, 100, (1, 1), 0)])])
    # a malformed operation makes its set invalid: forced past check_valid
    w.close([("A", [("offer", usd, usd, 100, (1, 1), 0)])], validate=False)
    return {"MANAGE_OFFER_LINE_FULL", "MANAGE_OFFER_BUY_NO_TRUST", "MANAGE_OFFER_MALFORMED"}


CASES = {name[5:]: fn for name, fn in sorted(globals().items()) if name.startswith("case_")}


@pytest.mark.parametrize("case", sorted(CASES))
def test_operation_outcomes(world, case):
    promised = CASES[case](world)
    assert promised <= world.seen, promised - world.seen


# -- the write-back buffer inside one close ------------------------------------------------------------


def in_order(w, make, wanted):
    """Close ``make(salt)`` for the first salt whose apply order passes
    ``wanted`` (the order is fixed by hashes: vary a transaction and look)."""
    for salt in range(64):
        done = w.close(make(salt), before=wanted)
        if done is not None:
            return done
    raise AssertionError("no apply order as wanted in 64 tries")


def first_then(first: str, then: str):
    return lambda order: [t.ops[0][0] for t in order if t.ops[0][0] in (first, then)][0] == first


def buffer_offer_created_and_crossed_in_one_close(w):
    usd = market(w)
    done = in_order(
        w,
        lambda salt: [("B", [("offer", usd, None, 1000, (101, 100), 0)]),
                      ("A", [("path", w.n("D"), None, 2000 + salt, usd, 400, ())])],
        first_then("offer", "path"),
    )
    assert [c for _tx, c in done] == [("txSUCCESS", ["MANAGE_OFFER_SUCCESS"]), ("txSUCCESS", ["PATH_PAYMENT_SUCCESS"])]
    assert [o[3] for o in w.plain.offers.values()] == [600]


def buffer_offer_deleted_is_not_seen_by_a_later_crossing(w):
    usd = market(w)
    asks(w, usd, ("B", 1000, 101), ("C", 1000, 102))
    in_order(
        w,
        lambda salt: [("B", [("offer", usd, None, 0, (101, 100), 1)]),
                      ("A", [("path", w.n("D"), None, 2000 + salt, usd, 400, ())])],
        first_then("offer", "path"),
    )
    assert {i: o[3] for i, o in w.plain.offers.items()} == {2: 600}


def buffer_failed_transaction_leaves_its_fee_alone(w):
    usd = market(w)
    before = w.plain.accounts[w.n("E")][0]
    w.close([("E", [("trust", usd, BIG), ("offer", None, usd, 500, (101, 100), 0), ("pay", w.n("A"), 2 * START, None)]),
             ("A", [("pay", w.n("E"), 5, None)])])
    e = w.n("E")
    assert "PAYMENT_UNDERFUNDED" in w.seen and "txFAILED" in w.seen
    assert (e, usd) not in w.plain.trustlines and not w.plain.offers and not w.plain.subentries.get(e)
    assert w.plain.accounts[e][0] == before - 3 * w.fee + 5


def buffer_failed_offer_leaves_nothing_of_its_crossings(w):
    """An arriving offer crosses B's ask, then meets its own and fails; a
    payment to B later in the same close must find B as it was (the node
    kept B's aborted sub-entry count and balance in its entry cache until
    ISSUE 30: ``ManageOfferOpFrame`` never rolled its inner delta back)."""
    usd = market(w)
    asks(w, usd, ("B", 1000, 101), ("A", 1000, 102))
    done = in_order(
        w,
        lambda salt: [("A", [("offer", None, usd, 5000 + salt, (97, 100), 0)]),
                      ("C", [("pay", w.n("B"), 7, None)])],
        first_then("offer", "pay"),
    )
    assert done[0][1] == ("txFAILED", ["MANAGE_OFFER_CROSS_SELF"])
    assert w.plain.subentries[w.n("B")] == 2 and len(w.plain.offers) == 2


BUFFER = {name[7:]: fn for name, fn in sorted(globals().items()) if name.startswith("buffer_")}


@pytest.mark.parametrize("case", sorted(BUFFER))
def test_store_buffer(world, case):
    BUFFER[case](world)


# -- seeded mixed closes: the benchmark's planner, two backends, both plain ledgers -----------------

SHAPE = {
    "shares": {"trust": 0.15, "credit": 0.075, "path": 0.075, "offer": 0.10, "native": 0.50, "create": 0.05,
               "options": 0.05},
    "failing_share": 0.03, "two_conversion_share": 0.1, "offer_by_id_share": 0.2, "delete_share_of_by_id": 0.25,
    "price_ladder": [97, 98, 99, 100, 101, 102, 103], "price_denominator": 100, "native_amount": 1000,
    "create_balance": 10**9, "credit_amount": [1000, 100000], "path_amount": [2000, 10000],
    "offer_take": [2000, 10000], "offer_rest": [20000, 30000], "credit_holding": 10**8, "best_level_target": 60000,
}
SHAPES = {
    "the-mix": {},
    "book-heavy": {"shares": {"trust": 0.05, "credit": 0.05, "path": 0.30, "offer": 0.40, "native": 0.10,
                              "create": 0.05, "options": 0.05}, "two_conversion_share": 0.4},
    "failing-heavy": {"failing_share": 0.25, "shares": {"trust": 0.10, "credit": 0.20, "path": 0.20, "offer": 0.20,
                                                        "native": 0.20, "create": 0.05, "options": 0.05}},
}
WIDTH, ACCOUNTS, ISSUERS = 16, 32, 4


@pytest.fixture(scope="module")
def two_backends(tmp_path_factory):
    """A ``cpu`` node and a ``tpu``-backend node (the XLA lowering of the
    verify kernel on the CPU; cutover 8, so a set of 16 is a device batch)
    with the benchmark's set-up state at a small size."""
    w = World(tmp_path_factory.mktemp("mixed"), 212, ("cpu", "tpu"))
    accounts = [w.n("acct %d" % i) for i in range(ACCOUNTS)]
    issuers = [w.n("issuer %d" % i) for i in range(ISSUERS)]
    signer_keys = {a: w.n("signer %d" % i) for i, a in enumerate(accounts)}
    w.close([(w.root, [("create", a, 10**11) for a in accounts + issuers])])
    # ``w.copy`` follows the order the nodes applied: it gives the phases their sequence numbers
    for phase in MC.set_up_phases(w.copy, accounts, issuers, 2, SHAPE, w.fee, random.Random(5)):
        for start in range(0, len(phase), WIDTH):
            part = [Tx(t.source, t.seq, t.fee, t.ops, t.signed_by) for t in phase[start : start + WIDTH]]
            assert all(codes[0] == "txSUCCESS" for _tx, codes in w.close(part))
    w.accounts, w.issuers, w.signer_keys = accounts, issuers, signer_keys
    yield w
    w.stop()


def levels(ledger) -> dict:
    """(selling, buying, price numerator) -> what rests there."""
    out = {}
    for _seller, selling, buying, amount, n, _d in ledger.offers.values():
        out[(selling, buying, n)] = out.get((selling, buying, n), 0) + amount
    return out


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_seeded_mixed_closes(two_backends, shape, seed):
    """Three closes of the planner's sets: both nodes, the plain ledger and
    the benchmark's copy agree on every code and row (``World.close``)."""
    w = two_backends
    w.seen.clear()
    planner = MC.Planner(
        copy.deepcopy(w.copy), w.accounts, w.issuers, w.signer_keys, lambda i: w.n("created %s %d %d" % (shape, seed, i)),
        w.fee, dict(SHAPE, **SHAPES[shape]), seed,
    )
    whats = set()
    exchange = [dict(n.lm.exchange_stats) for n in w.nodes]
    for _ in range(3):
        seq = w.nodes[0].lm.current.header.ledgerSeq
        planned = planner.plan(WIDTH, seq)
        whats.update(what for _tx, what in planned)
        done = w.close([Tx(tx.source, tx.seq, tx.fee, tx.ops, tx.signed_by) for tx, _what in planned])
        # what the planner believes is what came out, whatever the order was:
        # the same transactions fail, and every price level holds the same amount
        failed = {tx.source for tx, codes in done if codes[0] != "txSUCCESS"}
        assert failed == {tx.source for tx, what in planned if what.startswith("fail:")}
        assert levels(planner.ledger) == levels(w.plain)
    assert {"txSUCCESS", "PAYMENT_SUCCESS", "MANAGE_OFFER_SUCCESS"} <= w.seen
    if shape == "failing-heavy":
        assert "txFAILED" in w.seen and any(what.startswith("fail:") for what in whats)
    if shape == "book-heavy":
        assert "PATH_PAYMENT_SUCCESS" in w.seen
    # the device verified: the tpu-backend node's sets are over its cutover
    assert w.nodes[1].app.sig_backend.stats()["device_calls"] > 0
    # a page of five costs what it needs, not a side: the book's rows are
    # read once a (side, close), whatever the close holds pending by then
    for node, before in zip(w.nodes, exchange):
        did = {k: v - before[k] for k, v in node.lm.exchange_stats.items()}
        assert did["conversions"] > 0 and 0 < did["book_side_loads"] <= did["book_pages"]
        assert did["book_rows"] / did["book_pages"] < 20, did


# -- spans, attributes, counters -------------------------------------------------------------------------


def test_spans_and_counters_of_a_mixed_close(world):
    w = world
    usd = market(w)
    asks(w, usd, ("B", 100, 101), ("B", 100, 101), ("B", 100, 101), ("B", 100, 101), ("B", 100, 101), ("C", 1000, 102))
    app = w.nodes[0].app
    info = lambda: app.command_handler.handle_info({})["info"]["exchange"]  # noqa: E731
    before = info()
    assert set(before) == {
        "conversions", "offers_crossed", "book_pages", "book_rows", "book_side_loads", "txs_failed_at_apply",
        "payments_applied",
    }
    app.tracer.clear()
    w.close([("A", [("path", w.n("D"), None, 9000, usd, 700, ())]),  # six offers, two pages
             ("D", [("trust", w.asset("EUR", "J"), BIG)]),
             ("E", [("pay", w.n("A"), 2 * START, None)])])  # fails at apply
    spans, _, _ = app.tracer.snapshot(clear=True)
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    (exchange,) = by_name["op.exchange"]
    assert exchange.attrs["crossed"] == 6 and exchange.attrs["pages"] == 2
    # the first page reads the side, six rows, once; the second is a slice of
    # what the close's buffer holds of it (no offer of this book is pending
    # as an upsert until the sixth is reduced, after the last page)
    assert exchange.attrs["rows"] == 6 and exchange.attrs["side_loads"] == 1
    (serial,) = by_name["apply.serial"]
    # E's payment reaches its body (and fails there); A's path payment has no PAYMENT
    assert serial.attrs == {"txs": 3, "accounts": 3, "failed": 1, "payments": 1}
    (sampled,) = by_name["tx.apply"]  # index 0 of the set
    assert sampled.attrs["op"] in ("PATH_PAYMENT", "CHANGE_TRUST", "PAYMENT")
    (flush,) = by_name["commit.flush"]
    # B's, C's and D's lines and D's new one; five asks deleted and one reduced
    assert flush.attrs["trust_rows"] == 4 and flush.attrs["offer_rows"] == 6
    assert flush.attrs["signer_rows"] == 0
    after = info()
    assert {k: after[k] - before[k] for k in after} == {
        "conversions": 1, "offers_crossed": 6, "book_pages": 2, "book_rows": 6, "book_side_loads": 1,
        "txs_failed_at_apply": 1, "payments_applied": 1,
    }
    # one span a conversion beside the close's budget (tests/test_trace.py)
    assert len(by_name["op.exchange"]) == after["conversions"] - before["conversions"]


def test_a_close_reads_each_side_of_the_book_once(world, monkeypatch):
    """Fifty-two conversions over four sides in one close: one ``SELECT`` a
    side, whatever the pages; and every page asked for twice, the twin's
    frames changed as the exchange changes what it crosses — the close is
    still the plain ledger's, so nothing handed out is handed out again."""
    from stellar_tpu.ledger.offerframe import OfferFrame
    from stellar_tpu.xdr.entries import Price

    w = world
    usd, eur = market(w), w.asset("EUR", "J")
    w.close([(x, [("trust", eur, BIG)]) for x in "ABCD"])
    w.close([("J", [("pay", w.n(x), HELD, eur) for x in "ABCD"])])
    # B and C rest asks and bids in both credits, four price levels each
    for seller in "BC":
        w.close([(seller, [("offer", *pair, 700, (n, 100), 0) for n in (101, 102, 103, 104)])
                 for credit in (usd, eur) for pair in ((credit, None), (None, credit))])
    assert len(w.plain.offers) == 32
    node = w.nodes[0]
    sides, pages = set(), []
    one_page = OfferFrame.load_best_offers.__func__

    def asked_twice(cls, num, offset, selling, buying, db, tally=None):
        page = one_page(cls, num, offset, selling, buying, db, tally)
        twin = one_page(cls, num, offset, selling, buying, db)
        for a, b in zip(page, twin, strict=True):
            assert a is not b and a.entry is not b.entry and a.entry == b.entry
            b.mut().amount = 0
            b.mut().price = Price(b.offer.price.n + 1, b.offer.price.d)
        sides.add((selling.to_xdr(), buying.to_xdr()))
        pages.append(len(page))
        return page

    monkeypatch.setattr(OfferFrame, "load_best_offers", classmethod(asked_twice))
    selects = []
    node.app.database._conn.set_trace_callback(
        lambda sql: selects.append(sql) if "FROM offers" in sql and "ORDER BY price" in sql else None
    )
    before = dict(node.lm.exchange_stats)
    # A and D pay each other through each of the four books in turn
    plan = []
    for i in range(52):
        payer, payee = ("A", "D") if i % 2 else ("D", "A")
        credit = (usd, eur)[i // 2 % 2]
        sent, got = ((None, credit), (credit, None))[i // 4 % 2]
        plan.append((payer, [("path", w.n(payee), sent, 2000, got, 100 * (1 + i % 5), ())]))
    done = w.close(plan)
    node.app.database._conn.set_trace_callback(None)
    did = {k: v - before[k] for k, v in node.lm.exchange_stats.items()}
    assert sum(1 for _tx, codes in done if codes[0] == "txSUCCESS") >= 40
    assert did["conversions"] == 52 and len(sides) == 4 and did["offers_crossed"] >= 52
    assert did["book_pages"] == len(pages) >= 52
    # one read a side: the SQL statements against the book are those and no others
    assert did["book_side_loads"] == len(selects) == 4
    assert not any(" LIMIT " in sql for sql in selects)
    # 32 rows read once, and the few pending offers of its own book a page
    assert did["book_rows"] / did["book_pages"] < 5, did


def test_the_readers_tables_are_the_programs():
    """``reference_mixed``'s hand-written code tables against the program's enums."""
    from stellar_tpu.xdr import txs

    assert RM.TX_CODES == {int(c): c.name for c in txs.TransactionResultCode}
    assert RM.OP_CODES == {int(c): c.name for c in txs.OperationResultCode if c.name != "opINNER"}
    enums = {"CREATE_ACCOUNT": txs.CreateAccountResultCode, "PAYMENT": txs.PaymentResultCode,
             "PATH_PAYMENT": txs.PathPaymentResultCode, "MANAGE_OFFER": txs.ManageOfferResultCode,
             "SET_OPTIONS": txs.SetOptionsResultCode, "CHANGE_TRUST": txs.ChangeTrustResultCode}
    for op_type, (prefix, names, _more) in RM.INNER_CODES.items():
        assert txs.OperationType(op_type).name == prefix
        assert {-i: prefix + "_" + n for i, n in enumerate(names)} == {int(c): c.name for c in enums[prefix]}
