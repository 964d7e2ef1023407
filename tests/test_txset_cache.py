"""The herder's tx-set cache keeps a closed slot's set as its wire bytes
(``herder/pendingenvelopes.py`` ``slot_closed``; ``TxSetFrame.wire_bytes``).

A set is frames — the very frames that were put, verdict memo and all —
while a slot can still ask about it; once this node has closed a ledger on
top of the set's ``previous_ledger_hash`` the cache holds the packed
``TransactionSet`` under the same hash in the same place of the LRU.  What
is sent, stored and closed is byte for byte what it was.
"""

from __future__ import annotations

import base64
import gc

import pytest

from stellar_tpu.crypto.keys import SecretKey, verify_cache
from stellar_tpu.herder import TX_STATUS_PENDING, Herder
from stellar_tpu.herder.pendingenvelopes import _LRU, TXSET_CACHE_SIZE
from stellar_tpu.herder.txset import TxSetFrame
from stellar_tpu.ledger.accountframe import AccountFrame
from stellar_tpu.ledger.headerframe import LedgerHeaderFrame
from stellar_tpu.main.application import Application
from stellar_tpu.main.persistentstate import K_LAST_SCP_DATA
from stellar_tpu.simulation import OVER_LOOPBACK, Simulation
from stellar_tpu.tx import testutils as T
from stellar_tpu.util import VIRTUAL_TIME, VirtualClock
from stellar_tpu.xdr.base import pack_var_array_of, unpack_var_arrays
from stellar_tpu.xdr.ledger import StellarValue, TransactionSet
from stellar_tpu.xdr.overlay import MessageType, StellarMessage
from stellar_tpu.xdr.scp import SCPEnvelope, SCPQuorumSet


class Node:
    """One standalone validator under ``MANUAL_CLOSE`` with funded accounts:
    ``close`` offers transactions and closes the next ledger the way the
    front door does — ``trigger_next_ledger`` → SCP → close."""

    def __init__(self, instance: int, accounts: int = 0, limit: int = 0):
        self.clock = VirtualClock(VIRTUAL_TIME)
        cfg = T.get_test_config(instance)
        cfg.MANUAL_CLOSE = True
        self.app = Application.create(self.clock, cfg, new_db=True)
        self.app.start()
        self.lm = self.app.ledger_manager
        self.herder = self.app.herder
        self.cache = self.herder.pending_envelopes
        if limit:
            self.lm.current.header.maxTxSetSize = limit
        root = T.root_key_for(self.app)
        seq = AccountFrame.load_account(root.get_public_key(), self.app.database).get_seq_num()
        self.keys = [T.get_account(f"cache-{i}") for i in range(accounts)]
        for i, k in enumerate(self.keys):
            T.apply_tx(self.app, T.tx_from_ops(self.app, root, seq + 1 + i, [T.create_account_op(k, 10**9)]))
        self.seqs = [self.seq_of(k) for k in self.keys]
        # (hash, the set packed by the XDR codec, the frame) at every put
        self.puts = []
        self.keep_frames = True
        inner = self.cache.recv_tx_set

        def recv_tx_set(ts_hash, txset):
            self.puts.append((ts_hash, txset.to_xdr().to_xdr(), txset if self.keep_frames else None))
            inner(ts_hash, txset)

        self.cache.recv_tx_set = recv_tx_set

    def seq_of(self, key) -> int:
        return AccountFrame.load_account(key.get_public_key(), self.app.database).get_seq_num()

    def payment(self, i: int, amount: int = 1000):
        self.seqs[i] += 1
        return T.tx_from_ops(self.app, self.keys[i], self.seqs[i], [T.payment_op(self.keys[i ^ 1], amount)])

    def close(self, txs=()) -> None:
        for tx in txs:
            assert self.herder.recv_transaction(tx) == TX_STATUS_PENDING
        start = self.lm.get_last_closed_ledger_num()
        self.herder.trigger_next_ledger(self.lm.get_ledger_num())
        assert self.clock.crank_until(lambda: self.lm.get_last_closed_ledger_num() > start, 30)

    def close_payments(self) -> None:
        self.close([self.payment(i) for i in range(len(self.keys))])

    def info(self) -> dict:
        return self.app.command_handler.execute("info")["info"]["pending_envelopes"]

    def stop(self) -> None:
        self.app.graceful_stop()
        self.clock.shutdown()


@pytest.fixture()
def node(request):
    n = Node(*getattr(request, "param", (93,)))
    yield n
    n.stop()


# -- (a) a node that closes ledger after ledger ------------------------------------


@pytest.mark.parametrize("node", [(93, 60)], indirect=True)
def test_twelve_triggered_ledgers_leave_bytes_behind(node):
    """At every boundary at most the open slot's sets are frames, nothing
    was built back from bytes, and what the collector tracks does not grow
    with the ledgers closed (60 payments a ledger are ~2,000 tracked
    objects a ledger as frames: a fifth more by ledger 12)."""
    tracked = {}
    node.keep_frames = False
    for ledger in range(1, 13):
        node.close_payments()
        info = node.info()
        assert info["txsets_inflated"] <= 2, info
        assert info["txset_reinflations"] == 0, info
        assert info["txset_deflations"] == ledger
        assert info["txsets"] == info["txsets_inflated"] + info["txsets_deflated"] == ledger
        assert info["txset_bytes"] == sum(len(w) for _h, w, _f in node.puts)
        gc.collect()
        tracked[ledger] = len(gc.get_objects())
    assert abs(tracked[12] - tracked[4]) <= 0.05 * tracked[4], tracked
    # every set the node held it still holds, under its hash
    for ts_hash, wire, _frame in node.puts:
        assert ts_hash in node.cache.txset_cache
        assert node.cache.get_tx_set_wire(ts_hash) == wire
    assert node.info()["txset_reinflations"] == 0


# -- (b) what a closed slot's set comes back as --------------------------------------


def multisig_txs(node):
    """Account 0 gains a second signer and a medium threshold of two in a
    ledger of its own; then a payment that needs, and carries, both
    signatures."""
    second = T.get_account("cache-second-signer")
    from stellar_tpu.xdr.txs import Signer

    node.seqs[0] += 1
    node.close(
        [
            T.tx_from_ops(
                node.app, node.keys[0], node.seqs[0],
                [T.set_options_op(med=2, signer=Signer(second.get_public_key(), 1))],
            )
        ]
    )
    tx = node.payment(0)
    tx.add_signature(second)
    return [tx, node.payment(2)]


@pytest.mark.parametrize("node", [(94, 4)], indirect=True)
@pytest.mark.parametrize("what", ["empty", "one_tx", "multisig"])
def test_closed_set_comes_back_equal(node, what):
    txs = {"empty": lambda: [], "one_tx": lambda: [node.payment(1)], "multisig": lambda: multisig_txs(node)}[what]()
    node.puts.clear()
    node.close(txs)
    ((ts_hash, wire, put),) = node.puts
    assert ts_hash == node.lm.last_closed.header.scpValue.txSetHash
    assert put.size() == len(txs)
    assert isinstance(node.cache.txset_cache.d[ts_hash], bytes)
    back = node.cache.get_tx_set(ts_hash)
    assert back is not put and node.cache.txset_reinflations == 1
    assert back.get_contents_hash() == ts_hash
    assert back.previous_ledger_hash == put.previous_ledger_hash
    assert back.wire_bytes() == back.to_xdr().to_xdr() == wire
    assert [t.get_full_hash() for t in back.transactions] == [t.get_full_hash() for t in put.transactions]
    assert [len(t.envelope.signatures) for t in back.transactions] == [len(t.envelope.signatures) for t in put.transactions]
    # frames are not put back: the next question builds them again
    assert isinstance(node.cache.txset_cache.d[ts_hash], bytes)
    assert node.cache.get_tx_set(ts_hash) is not back and node.cache.txset_reinflations == 2


@pytest.mark.parametrize("node", [(95, 4)], indirect=True)
def test_wire_bytes_are_the_codecs_and_follow_the_set(node):
    ts = TxSetFrame(node.lm.last_closed.hash, [node.payment(i) for i in range(3)])
    first = ts.wire_bytes()
    assert first == ts.to_xdr().to_xdr() and ts.wire_bytes() is first
    ts.sort_for_hash()
    ts.get_contents_hash()
    assert ts.wire_bytes() is first  # a sort that moves nothing keeps them
    extra = node.payment(3)
    ts.add_transaction(extra)
    assert ts.wire_bytes() == ts.to_xdr().to_xdr() != first
    ts.remove_tx(extra)
    assert ts.wire_bytes() == first
    ts.transactions.reverse()
    ts.sort_for_hash()
    assert ts._wire is None and ts.wire_bytes() == first
    again = TxSetFrame.from_wire(node.app.network_id, first)
    assert again.get_contents_hash() == ts.get_contents_hash() and again.wire_bytes() is first


# -- (c) the open slot's set stays the frame that was put -------------------------


@pytest.mark.parametrize("node", [(96, 10, 5)], indirect=True)
def test_open_slots_set_keeps_identity_and_verdict(node):
    """A backlog of twice the set limit, as ``TestTriggeredLedgerValidatesOnce``:
    every question SCP asks about the proposed set finds the frame the
    trigger put, and the ledger still costs two full passes."""
    seen = []
    inner = node.herder.validate_value

    def validate_value(slot_index, value):
        frame = node.cache.get_tx_set(StellarValue.from_xdr(value).txSetHash)
        seen.append((frame, frame._valid_on))
        return inner(slot_index, value)

    node.herder.scp.driver.validate_value = validate_value
    before = dict(node.lm.txset_validations)
    lcl = node.lm.last_closed.hash
    node.close([node.payment(i) for i in range(10)])
    ((ts_hash, _wire, put),) = node.puts
    assert len(seen) >= 7
    assert all(frame is put for frame, _ in seen)
    assert all(memo == (node.lm, lcl) for _, memo in seen)
    did = {k: v - before[k] for k, v in node.lm.txset_validations.items()}
    assert did == {"full": 2, "memo": 7, "trim_memo": 1}
    info = node.info()
    assert (info["txset_reinflations"], info["txset_deflations"], info["txsets_inflated"]) == (0, 1, 0)
    assert isinstance(node.cache.txset_cache.d[ts_hash], bytes)


# -- (d) which sets the rule leaves alone, and which it finds late --------------------


@pytest.mark.parametrize("node", [(97, 2)], indirect=True)
def test_a_set_on_a_ledger_not_closed_here_stays_frames(node):
    ahead = TxSetFrame(b"\x07" * 32, [])
    node.herder.recv_tx_set(ahead.get_contents_hash(), ahead)
    for _ in range(3):
        node.close_payments()
        # the next slot's set may arrive before this node proposes its own
        coming = TxSetFrame(node.lm.last_closed.hash, [])
        node.herder.recv_tx_set(coming.get_contents_hash(), coming)
        node.herder.ledger_closed()
        assert node.cache.get_tx_set(coming.get_contents_hash()) is coming
        assert node.cache.get_tx_set(ahead.get_contents_hash()) is ahead
    info = node.info()
    # the three closed slots' own sets and the first two `coming` ones
    assert (info["txsets_inflated"], info["txsets_deflated"]) == (2, 5)
    assert info["txset_reinflations"] == 0


@pytest.mark.parametrize("node", [(98, 2)], indirect=True)
def test_a_set_that_arrives_after_its_slot_closed_is_found_at_the_next_boundary(node):
    old = node.lm.last_closed.hash
    for _ in range(3):
        node.close_payments()
    late = TxSetFrame(old, [node.payment(0)])
    h = late.get_contents_hash()
    node.herder.recv_tx_set(h, late)
    assert node.cache.get_tx_set(h) is late
    node.close()
    assert node.cache.txset_cache.d[h] == late.wire_bytes()
    assert node.info()["txsets_inflated"] == 0


@pytest.mark.parametrize("node", [(99, 2)], indirect=True)
def test_ledgers_closed_between_two_boundaries_are_known(node):
    """A catch-up closes its buffered ledgers back to back and tells the
    herder once: the sets built on the ledgers in between are sets of
    closed slots too."""
    node.close_payments()
    held = []
    for _ in range(3):
        ts = TxSetFrame(node.lm.last_closed.hash, [])
        node.herder.recv_tx_set(ts.get_contents_hash(), ts)
        held.append(ts)
        T.close_ledger_on(node.app, node.lm.last_closed.header.scpValue.closeTime + 5)
    assert all(node.cache.get_tx_set(ts.get_contents_hash()) is ts for ts in held)
    node.herder.ledger_closed()
    assert all(isinstance(node.cache.txset_cache.d[ts.get_contents_hash()], bytes) for ts in held)
    assert node.info()["txsets_inflated"] == 0


# -- (e) the LRU's contract with entries of both forms -------------------------------


@pytest.mark.parametrize("node", [(100, 2)], indirect=True)
def test_lru_order_membership_and_eviction_with_both_forms(node):
    assert node.cache.txset_cache.cap == TXSET_CACHE_SIZE == 10000
    cache = node.cache.txset_cache = _LRU(4)
    hashes = []
    for _ in range(3):
        node.close_payments()
        hashes.append(node.lm.last_closed.header.scpValue.txSetHash)
    ahead = TxSetFrame(b"\x09" * 32, [])
    hashes.append(ahead.get_contents_hash())
    node.herder.recv_tx_set(hashes[3], ahead)
    assert list(cache.d) == hashes and all(h in cache for h in hashes)
    assert [type(v) for v in cache.d.values()] == [bytes, bytes, bytes, TxSetFrame]
    # a read of either form, by either reader, moves the entry to the young end
    assert node.cache.get_tx_set_wire(hashes[0]) is cache.d[hashes[0]]
    assert node.cache.get_tx_set(hashes[1]).get_contents_hash() == hashes[1]
    assert node.cache.get_tx_set(hashes[3]) is ahead
    assert list(cache.d) == [hashes[2], hashes[0], hashes[1], hashes[3]]
    # the oldest entry goes, whatever its form; deflating moves nothing
    node.close_payments()
    newest = node.lm.last_closed.header.scpValue.txSetHash
    assert hashes[2] not in cache and node.cache.get_tx_set(hashes[2]) is None
    assert node.cache.get_tx_set_wire(hashes[2]) is None
    assert list(cache.d) == [hashes[0], hashes[1], hashes[3], newest]
    for _ in range(2):
        node.close_payments()
    assert hashes[0] not in cache and hashes[1] not in cache and hashes[3] in cache
    assert len(cache.d) == 4 == node.info()["txsets"]


# -- (f) a network: sets of closed slots served and fetched ---------------------------


class Wire:
    """Every TX_SET body any node of the process sends, by set hash."""

    def __init__(self, monkeypatch):
        from stellar_tpu.overlay.peer import Peer

        self.sent = {}
        inner = Peer.send_message

        def send_message(peer, msg, body=None):
            if msg.type == MessageType.TX_SET:
                packed = body if body is not None else msg.to_xdr()
                decoded = StellarMessage.from_xdr(packed)
                frame = TxSetFrame.from_xdr_set(peer.app.network_id, decoded.value)
                self.sent.setdefault(frame.get_contents_hash(), []).append((peer.app, packed))
            inner(peer, msg, body)

        monkeypatch.setattr(Peer, "send_message", send_message)


def network(n: int, threshold: int):
    keys = [SecretKey.pseudo_random_for_testing(50 + i) for i in range(n)]
    qset = SCPQuorumSet(threshold, [k.get_public_key() for k in keys], [])
    sim = Simulation(OVER_LOOPBACK, VirtualClock())
    for i, k in enumerate(keys):
        cfg = T.get_test_config(i)
        cfg.MANUAL_CLOSE = False
        cfg.ARTIFICIALLY_ACCELERATE_TIME_FOR_TESTING = True
        sim.add_node(k, qset, cfg=cfg)
    for i in range(n):
        for j in range(i + 1, n):
            sim.add_pending_connection(keys[i], keys[j])
    sim.start_all_nodes()
    return sim, keys


def stop(sim):
    sim.stop_all_nodes()
    sim.clock.shutdown()
    verify_cache().clear()


def offer_payments(app, count: int) -> None:
    """``count`` payments from the root account, pending at ``app``."""
    root = T.root_key_for(app)
    seq = max(
        AccountFrame.load_account(root.get_public_key(), app.database).get_seq_num(),
        app.herder.get_max_seq_in_pending_txs(root.get_public_key()),
    )
    for i in range(count):
        dest = T.get_account(f"net-{seq + i}")
        tx = T.tx_from_ops(app, root, seq + 1 + i, [T.create_account_op(dest, 10**9)])
        assert app.herder.recv_transaction(tx) == TX_STATUS_PENDING


def chain(app) -> list:
    last = app.ledger_manager.get_last_closed_ledger_num()
    return [LedgerHeaderFrame.load_by_sequence(app.database, s).get_hash() for s in range(1, last + 1)]


def test_peer_asks_for_a_set_two_slots_closed(monkeypatch):
    wire = Wire(monkeypatch)
    sim, keys = network(2, 2)
    try:
        a, b = (sim.get_node(k) for k in keys)
        offer_payments(a, 3)
        assert sim.crank_until(lambda: sim.have_all_externalized(5), 120)
        seq = a.ledger_manager.get_last_closed_ledger_num() - 2
        funded = [
            s for s in range(2, seq + 1)
            if LedgerHeaderFrame.load_by_sequence(a.database, s).header.scpValue.txSetHash in wire.sent
        ]
        ts_hash = LedgerHeaderFrame.load_by_sequence(a.database, funded[-1]).header.scpValue.txSetHash
        sent_open = [body for _app, body in wire.sent[ts_hash]]
        assert sent_open and len(set(sent_open)) == 1
        for app in (a, b):
            assert isinstance(app.herder.pending_envelopes.txset_cache.d[ts_hash], bytes)
        before = len(wire.sent[ts_hash])
        reinflated = a.herder.pending_envelopes.txset_reinflations
        (asker,) = b.overlay_manager.authenticated_peers()
        asker.send_get_tx_set(ts_hash)
        assert sim.crank_until(lambda: len(wire.sent[ts_hash]) > before, 30)
        answered_by, body = wire.sent[ts_hash][-1]
        assert answered_by is a and body == sent_open[0]
        assert body == StellarMessage(
            MessageType.TX_SET, TxSetFrame.from_xdr_set(a.network_id, StellarMessage.from_xdr(body).value).to_xdr()
        ).to_xdr()
        assert a.herder.pending_envelopes.txset_reinflations == reinflated
        assert sim.all_ledgers_agree()
    finally:
        stop(sim)


def lagging_run(monkeypatch, deflate: bool):
    """Three validators, any two a quorum; the third hears nothing for two
    ledgers, then fetches what it missed — sets the others have closed —
    and closes them.  Each node's chain of ledger hashes, each node's
    counters, and the sets the two served after the third could hear."""
    from stellar_tpu.herder.pendingenvelopes import PendingEnvelopes

    if not deflate:
        monkeypatch.setattr(PendingEnvelopes, "_deflate_closed_tx_sets", lambda self: None)
    wire = Wire(monkeypatch)
    sim, keys = network(3, 2)
    try:
        apps = [sim.get_node(k) for k in keys]
        offer_payments(apps[0], 2)
        assert sim.crank_until(lambda: sim.have_all_externalized(3), 120)
        sim.partition([keys[2]], keys[:2], oneway=True)
        behind = apps[2].ledger_manager.get_last_closed_ledger_num()
        offer_payments(apps[0], 2)
        assert sim.crank_until(lambda: apps[0].ledger_manager.get_last_closed_ledger_num() >= behind + 2, 120)
        assert apps[2].ledger_manager.get_last_closed_ledger_num() == behind
        missed = {
            LedgerHeaderFrame.load_by_sequence(apps[0].database, s).header.scpValue.txSetHash
            for s in (behind + 1, behind + 2)
        }
        wire.sent.clear()
        sim.heal()
        assert sim.crank_until(lambda: sim.have_all_externalized(behind + 3), 120), sim.ledger_nums()
        assert sim.all_ledgers_agree()
        stats = [a.herder.pending_envelopes.dump_info() for a in apps]
        # (the first of the two may have reached the third before it went deaf)
        served = {h for h, sends in wire.sent.items() if h in missed and any(app in apps[:2] for app, _ in sends)}
        return [chain(a)[: behind + 3] for a in apps], stats, bool(served)
    finally:
        stop(sim)


def test_lagging_node_closes_to_the_same_hashes(monkeypatch):
    chains, stats, served_missed = lagging_run(monkeypatch, deflate=True)
    assert chains[0] == chains[1] == chains[2]
    assert served_missed
    assert all(s["txset_deflations"] >= 3 for s in stats), stats
    assert all(s["txsets_inflated"] <= 3 for s in stats), stats
    # the two that served their closed slots' sets built no frames for it
    assert [s["txset_reinflations"] for s in stats[:2]] == [0, 0], stats
    kept, kept_stats, _ = lagging_run(monkeypatch, deflate=False)
    assert all(s["txset_deflations"] == 0 for s in kept_stats)
    assert kept == chains


# -- (g) the persisted SCP state ------------------------------------------------------


def parents_blob(herder, slot: int) -> str:
    """``persist_scp_state`` as it was before the cache held bytes: every
    set fetched as frames and packed through the codec."""
    from stellar_tpu.scp.slot import Slot
    from stellar_tpu.scp.quorum import qset_hash

    envs = herder.scp.get_latest_messages_send(slot)
    txsets, qsets = {}, {}
    for e in envs:
        for v in Slot.statement_values(e.statement):
            h = StellarValue.from_xdr(v).txSetHash
            ts = herder.pending_envelopes.get_tx_set(h)
            if ts is not None:
                txsets[h] = ts
        qh = Slot.companion_qset_hash(e.statement)
        if qh is not None and herder.pending_envelopes.get_qset(qh) is not None:
            qsets[qh] = herder.pending_envelopes.get_qset(qh)
            assert qset_hash(qsets[qh]) == qh
    blob = (
        pack_var_array_of(SCPEnvelope, envs)
        + pack_var_array_of(TransactionSet, [t.to_xdr() for t in txsets.values()])
        + pack_var_array_of(SCPQuorumSet, list(qsets.values()))
    )
    return base64.b64encode(blob).decode()


@pytest.mark.parametrize("node", [(101, 6)], indirect=True)
def test_persisted_scp_state_is_the_parents_blob(node):
    node.close_payments()
    node.close_payments()
    slot = node.lm.get_last_closed_ledger_num()
    stored = node.app.persistent_state.get_state(K_LAST_SCP_DATA)
    # as the slot's last envelope left it, and again now that the set is bytes
    assert stored == parents_blob(node.herder, slot)
    ts_hash = node.lm.last_closed.header.scpValue.txSetHash
    assert isinstance(node.cache.txset_cache.d[ts_hash], bytes)
    reinflated = node.cache.txset_reinflations
    node.app.persistent_state.set_state(K_LAST_SCP_DATA, "")
    node.herder.persist_scp_state(slot)
    assert node.app.persistent_state.get_state(K_LAST_SCP_DATA) == stored
    assert node.cache.txset_reinflations == reinflated
    envs, sets, _qsets = unpack_var_arrays(base64.b64decode(stored), (SCPEnvelope, TransactionSet, SCPQuorumSet))
    assert envs and [len(s.txs) for s in sets] == [6]

    # a restart: the restored set is frames until the next boundary finds
    # its slot closed, and the state written from the bytes is the same
    again = Herder(node.app)
    again.restore_scp_state()
    assert again.pending_envelopes.dump_info()["txsets_inflated"] == 1
    again.pending_envelopes.slot_closed(slot)
    info = again.pending_envelopes.dump_info()
    assert (info["txsets_inflated"], info["txsets_deflated"], info["txset_deflations"]) == (0, 1, 1)
    node.app.persistent_state.set_state(K_LAST_SCP_DATA, "")
    again.persist_scp_state(slot)
    assert node.app.persistent_state.get_state(K_LAST_SCP_DATA) == stored
    assert again.pending_envelopes.txset_reinflations == 0


@pytest.mark.parametrize("form", ["frames", "bytes"])
def test_interrupted_close_replays_from_the_restored_set(form):
    """A node that died between externalizing a slot and committing its
    ledger finishes the close from the restored state — the same ledger,
    whether the cache hands the set out as the frames that were restored
    or builds it from bytes."""
    first = Node(102, 4)
    try:
        start = first.lm.last_closed.hash
        first.close_payments()
        blob = first.app.persistent_state.get_state(K_LAST_SCP_DATA)
        closed = first.lm.last_closed
    finally:
        first.stop()
    verify_cache().clear()
    second = Node(102, 4)
    try:
        assert second.lm.last_closed.hash == start
        second.app.persistent_state.set_state(K_LAST_SCP_DATA, blob)
        if form == "bytes":
            inner = second.cache.recv_tx_set

            def as_bytes(ts_hash, txset):
                inner(ts_hash, txset)
                second.cache.txset_cache.d[ts_hash] = txset.wire_bytes()

            second.cache.recv_tx_set = as_bytes
        second.herder.restore_scp_state()
        assert second.lm.last_closed.hash == closed.hash
        assert second.lm.last_closed.header.ledgerSeq == closed.header.ledgerSeq
        assert second.cache.txset_reinflations == (1 if form == "bytes" else 0)
    finally:
        second.stop()
