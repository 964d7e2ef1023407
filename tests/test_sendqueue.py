"""Overlay survival plane (stellar_tpu/overlay/sendqueue.py) — ISSUE r17.

Pins the tentpole contracts: class priority order, per-class byte/message
caps with shed-oldest for FLOOD/GOSSIP, CRITICAL never shed, straggler
disconnect (ERR_LOAD + peerrecord backoff) inside the stall budget,
drain-time MAC sequencing (priority reordering stays wire-valid),
pack-once buffer sharing across the flood fan-out, and the knob-off
(OVERLAY_SENDQ_BYTES=0) degeneration to the reference's immediate
unbounded sends — bit-exact at the frame level and behavior-exact on a
3-node consensus chain.
"""

from __future__ import annotations

import pytest

from stellar_tpu.crypto.sha import hmac_sha256
from stellar_tpu.main.application import Application
from stellar_tpu.main.config import Config
from stellar_tpu.overlay import (
    LoopbackPeerConnection,
    PeerRecord,
    PeerState,
)
from stellar_tpu.overlay.loopback import MAX_QUEUE_DEPTH
from stellar_tpu.overlay.sendqueue import (
    CLASS_CRITICAL,
    CLASS_FETCH,
    CLASS_FLOOD,
    CLASS_GOSSIP,
    classify,
)
from stellar_tpu.tx import testutils as T
from stellar_tpu.util import VirtualClock
from stellar_tpu.xdr.base import uint64, xdr_to_opaque
from stellar_tpu.xdr.overlay import (
    AuthenticatedMessage,
    Error,
    ErrorCode,
    MessageType,
    StellarMessage,
)


def make_app(clock, instance, sendq_bytes=None, flood_msgs=None,
             stall_ms=None, manual_close=True):
    cfg = T.get_test_config(instance)
    cfg.MANUAL_CLOSE = manual_close
    cfg.RUN_STANDALONE = True
    cfg.HTTP_PORT = 0
    if sendq_bytes is not None:
        cfg.OVERLAY_SENDQ_BYTES = sendq_bytes
    if flood_msgs is not None:
        cfg.OVERLAY_SENDQ_FLOOD_MSGS = flood_msgs
    if stall_ms is not None:
        cfg.STRAGGLER_STALL_MS = stall_ms
    app = Application.create(clock, cfg, new_db=True)
    app.start()
    return app


def crank(clock, n=80, budget=4.0):
    deadline = clock.now() + budget
    for _ in range(n):
        if clock.now() >= deadline:
            break
        nd = clock.next_deadline()
        if not clock.has_ready_work() and (nd is None or nd > deadline):
            break
        clock.crank()


def authed_pair(clock, a, b):
    conn = LoopbackPeerConnection(a, b)
    crank(clock)
    assert conn.initiator.is_authenticated()
    assert conn.acceptor.is_authenticated()
    return conn


def flood_msg(app, i=0):
    """A distinct structurally-valid TRANSACTION message (FLOOD class)."""
    from stellar_tpu.crypto.keys import SecretKey
    from stellar_tpu.tx.frame import TransactionFrame
    import stellar_tpu.xdr as X

    src = SecretKey.pseudo_random_for_testing(70_000_000 + i)
    dst = SecretKey.pseudo_random_for_testing(71_000_000 + i)
    tx = X.Transaction(
        sourceAccount=src.get_public_key(),
        fee=100,
        seqNum=1 + i,
        timeBounds=None,
        memo=X.Memo.none(),
        operations=[T.payment_op(dst, 1)],
        ext=0,
    )
    frame = TransactionFrame(app.network_id, X.TransactionEnvelope(tx, []))
    frame.add_signature(src)
    return frame.to_stellar_message()


def scp_msg(i=0):
    """A well-formed (garbage-signed) SCP envelope message (CRITICAL)."""
    from stellar_tpu.crypto.keys import SecretKey
    from stellar_tpu.xdr.scp import (
        SCPEnvelope,
        SCPNomination,
        SCPStatement,
        SCPStatementPledges,
        SCPStatementType,
    )

    sk = SecretKey.pseudo_random_for_testing(72_000_000 + i)
    st = SCPStatement(
        nodeID=sk.get_public_key(),
        slotIndex=1,
        pledges=SCPStatementPledges(
            SCPStatementType.SCP_ST_NOMINATE,
            SCPNomination(
                quorumSetHash=bytes([i % 256]) * 32, votes=[], accepted=[]
            ),
        ),
    )
    env = SCPEnvelope(statement=st, signature=bytes(64))
    return StellarMessage(MessageType.SCP_MESSAGE, env)


def fetch_msg(i=0):
    return StellarMessage(MessageType.GET_TX_SET, bytes([i % 256]) * 32)


def gossip_msg():
    return StellarMessage(MessageType.GET_PEERS, None)


def capture_frames(peer):
    """Intercept the transport hand-off (the queue's release point)."""
    sent = []
    orig = peer.send_frame

    def hook(data):
        sent.append(data)
        orig(data)

    peer.send_frame = hook
    return sent


def frame_type(data):
    from stellar_tpu.overlay.loopback import LoopbackPeer

    return LoopbackPeer._frame_msg_type(data)


# -- classification ----------------------------------------------------------


def test_classification_table():
    assert classify(MessageType.SCP_MESSAGE) == CLASS_CRITICAL
    assert classify(MessageType.HELLO2) == CLASS_CRITICAL
    assert classify(MessageType.AUTH) == CLASS_CRITICAL
    assert classify(MessageType.ERROR_MSG) == CLASS_CRITICAL
    assert classify(MessageType.GET_TX_SET) == CLASS_FETCH
    assert classify(MessageType.TX_SET) == CLASS_FETCH
    assert classify(MessageType.SCP_QUORUMSET) == CLASS_FETCH
    assert classify(MessageType.DONT_HAVE) == CLASS_FETCH
    assert classify(MessageType.GET_SCP_STATE) == CLASS_FETCH
    assert classify(MessageType.TRANSACTION) == CLASS_FLOOD
    assert classify(MessageType.GET_PEERS) == CLASS_GOSSIP
    assert classify(MessageType.PEERS) == CLASS_GOSSIP
    # unknown/future types ride FETCH: bounded but never shed
    assert classify(999) == CLASS_FETCH


# -- config validation -------------------------------------------------------


def test_config_knobs_validated_at_boot():
    for knob, bad in (
        ("OVERLAY_SENDQ_BYTES", -1),
        ("OVERLAY_SENDQ_BYTES", "lots"),
        ("OVERLAY_SENDQ_BYTES", True),
        ("OVERLAY_SENDQ_FLOOD_MSGS", 0),
        ("OVERLAY_SENDQ_FLOOD_MSGS", 2.5),
        ("STRAGGLER_STALL_MS", 0),
        ("STRAGGLER_STALL_MS", -5),
        ("STRAGGLER_STALL_MS", "slow"),
    ):
        cfg = Config()
        setattr(cfg, knob, bad)
        with pytest.raises(ValueError):
            cfg.validate()
    cfg = Config()
    cfg.OVERLAY_SENDQ_BYTES = 0  # off is legal
    cfg.STRAGGLER_STALL_MS = 250.5  # floats are legal
    cfg.validate()


# -- wire format: splice assembly is bit-exact -------------------------------


def test_drain_frame_bit_exact_vs_reference_assembly():
    """The queue splices frames from (disc | seq | shared-body | mac);
    they must be byte-identical to AuthenticatedMessage.v0_of(...).to_xdr()
    — the pre-r17 send_message construction — for MAC'd and unMAC'd
    messages alike."""
    clock = VirtualClock()
    a = make_app(clock, 60)
    b = make_app(clock, 61)
    try:
        conn = authed_pair(clock, a, b)
        peer = conn.initiator
        sent = capture_frames(peer)

        msg = gossip_msg()  # MAC'd
        seq = peer.send_mac_seq
        mac = hmac_sha256(
            peer.send_mac_key, xdr_to_opaque((uint64, seq), msg)
        )
        expected = AuthenticatedMessage.v0_of(seq, msg, mac).to_xdr()
        peer.send_message(msg)
        assert sent[-1] == expected

        err = StellarMessage(
            MessageType.ERROR_MSG, Error(ErrorCode.ERR_MISC, "x")
        )  # unMAC'd: seq 0, zero mac
        expected = AuthenticatedMessage.v0_of(0, err, b"\x00" * 32).to_xdr()
        peer.send_message(err)
        assert sent[-1] == expected
    finally:
        a.graceful_stop()
        b.graceful_stop()


# -- priority + caps ---------------------------------------------------------


def congested_pair(clock, a, b):
    """Authenticated pair with the initiator's delivery corked so credits
    never arrive: frames past the in-flight window stay queued."""
    conn = authed_pair(clock, a, b)
    conn.initiator.corked = True
    return conn


def fill_inflight(app, peer):
    """Stuff the transport window so the next enqueue actually queues."""
    sq = peer.send_queue
    i = 0
    while sq.queued_bytes == 0 and i < 600:
        peer.send_message(flood_msg(app, 500 + i))
        i += 1
    assert sq.queued_bytes > 0, "in-flight window never filled"


def test_class_priority_order_and_mac_seq_at_drain():
    """Messages enqueued GOSSIP→FLOOD→FETCH→CRITICAL under congestion
    must hit the wire CRITICAL→FETCH→FLOOD→GOSSIP — and because the MAC
    sequence is assigned at DRAIN time, the receiver accepts the
    reordered stream (the connection survives delivery)."""
    clock = VirtualClock()
    a = make_app(clock, 62, sendq_bytes=4096)
    b = make_app(clock, 63, sendq_bytes=4096)
    try:
        conn = congested_pair(clock, a, b)
        peer = conn.initiator
        fill_inflight(a, peer)
        sent = capture_frames(peer)
        peer.send_message(gossip_msg())
        peer.send_message(flood_msg(a, 0))
        peer.send_message(fetch_msg(1))
        peer.send_message(scp_msg(2))
        assert not sent, "congested queue must hold frames back"
        assert peer.send_queue.queued_bytes <= 4096

        conn.initiator.set_corked(False)
        crank(clock)
        kinds = [frame_type(d) for d in sent]
        probe = [
            k for k in kinds
            if k in (
                MessageType.SCP_MESSAGE,
                MessageType.GET_TX_SET,
                MessageType.GET_PEERS,
            ) or k == MessageType.TRANSACTION
        ]
        # CRITICAL first, then FETCH, then the flood backlog, gossip last
        assert probe[0] == MessageType.SCP_MESSAGE
        assert probe[1] == MessageType.GET_TX_SET
        assert probe[-1] == MessageType.GET_PEERS
        # the reordered stream is MAC-sequence valid end to end
        assert conn.acceptor.is_authenticated()
        assert conn.initiator.is_authenticated()
    finally:
        a.graceful_stop()
        b.graceful_stop()


def test_flood_msg_cap_sheds_oldest_within_class():
    clock = VirtualClock()
    a = make_app(clock, 64, sendq_bytes=1 << 20, flood_msgs=3)
    b = make_app(clock, 65, sendq_bytes=1 << 20, flood_msgs=3)
    try:
        conn = congested_pair(clock, a, b)
        peer = conn.initiator
        fill_inflight(a, peer)
        sq = peer.send_queue
        base_q = len(sq._q[CLASS_FLOOD])
        bodies = []
        for i in range(6):
            m = flood_msg(a, i)
            body = m.to_xdr()
            bodies.append(body)
            peer.send_message(m, body=body)
        q = sq._q[CLASS_FLOOD]
        assert len(q) == 3  # capped
        kept = [e[0] for e in list(q)[-3:]]
        assert kept == bodies[-3:]  # newest survive, oldest shed
        assert sq.shed_msgs[CLASS_FLOOD] >= 3 + base_q
        assert a.overlay_manager.sendq_stats.shed_msgs[CLASS_FLOOD] >= 3
        assert sq.shed_msgs[CLASS_CRITICAL] == 0
    finally:
        a.graceful_stop()
        b.graceful_stop()


def test_goodbye_error_frame_bypasses_a_congested_queue():
    """REVIEW r17 fix: drop(code) on a congested peer must hand the
    goodbye ERROR frame straight to the transport (the reference's
    direct write) — not queue it behind the congestion and then clear
    it in send_queue.close()."""
    clock = VirtualClock()
    a = make_app(clock, 92, sendq_bytes=4096, stall_ms=60_000)
    b = make_app(clock, 93, sendq_bytes=4096, stall_ms=60_000)
    try:
        conn = congested_pair(clock, a, b)
        peer = conn.initiator
        fill_inflight(a, peer)
        sent = capture_frames(peer)
        peer.drop(ErrorCode.ERR_MISC, "goodbye")
        assert MessageType.ERROR_MSG in [frame_type(d) for d in sent]
    finally:
        a.graceful_stop()
        b.graceful_stop()


def test_sent_meter_counts_wire_frames_not_shed_attempts():
    """REVIEW r17 fix: the per-peer 'message write' meter marks at the
    queue's DRAIN — a shed FLOOD frame never counts as sent, so the
    meter and bytes_send agree during exactly the congestion episodes
    they diagnose."""
    clock = VirtualClock()
    a = make_app(clock, 94, sendq_bytes=4096, flood_msgs=4)
    b = make_app(clock, 95, sendq_bytes=4096, flood_msgs=4)
    try:
        conn = congested_pair(clock, a, b)
        peer = conn.initiator
        fill_inflight(a, peer)
        sq = peer.send_queue
        n0 = peer._m_sent.count
        e0 = sq.n_emitted
        for i in range(20):
            peer.send_message(flood_msg(a, i))
        assert sq.shed_msgs[CLASS_FLOOD] > 0
        # nothing drained (window full): zero new wire frames counted
        assert peer._m_sent.count == n0
        conn.initiator.set_corked(False)
        crank(clock)
        # meter moved in lockstep with actual queue releases — the shed
        # frames are in neither
        assert peer._m_sent.count - n0 == sq.n_emitted - e0 > 0
    finally:
        a.graceful_stop()
        b.graceful_stop()


def test_byte_cap_sheds_flood_and_bounds_high_water():
    clock = VirtualClock()
    cap = 4096
    a = make_app(clock, 66, sendq_bytes=cap)
    b = make_app(clock, 67, sendq_bytes=cap)
    try:
        conn = congested_pair(clock, a, b)
        peer = conn.initiator
        sq = peer.send_queue
        for i in range(120):
            peer.send_message(flood_msg(a, i))
        assert sq.queued_bytes <= cap
        assert 0 < sq.bytes_high_water <= cap
        assert sq.shed_msgs[CLASS_FLOOD] > 0
        assert sq.shed_bytes[CLASS_FLOOD] > 0
        assert sq.shed_msgs[CLASS_CRITICAL] == 0
        assert a.overlay_manager.sendq_stats.bytes_high_water <= cap
    finally:
        a.graceful_stop()
        b.graceful_stop()


def test_gossip_push_never_evicts_queued_flood():
    """REVIEW r17 fix: a GOSSIP push may shed only its OWN class — a
    full queue of FLOOD frames is never displaced by lower-priority
    peer-address gossip; the gossip frame itself is the shed."""
    clock = VirtualClock()
    cap = 4096
    a = make_app(clock, 96, sendq_bytes=cap)
    b = make_app(clock, 97, sendq_bytes=cap)
    try:
        conn = congested_pair(clock, a, b)
        peer = conn.initiator
        sq = peer.send_queue
        for i in range(120):  # fill the queue to the cap with FLOOD
            peer.send_message(flood_msg(a, i))
        flood_before = len(sq._q[CLASS_FLOOD])
        shed_before = sq.shed_msgs[CLASS_FLOOD]
        assert flood_before > 0
        # a gossip frame bigger than any possible residual slack (the
        # pre-packed body never reaches the wire: it is the shed)
        gossip = StellarMessage(MessageType.PEERS, [])
        ok = sq.enqueue(gossip, body=b"\x00" * 1024)
        assert ok is False  # the gossip frame itself was the shed
        assert len(sq._q[CLASS_FLOOD]) == flood_before
        assert sq.shed_msgs[CLASS_FLOOD] == shed_before
        assert sq.shed_msgs[CLASS_GOSSIP] == 1
    finally:
        a.graceful_stop()
        b.graceful_stop()


def test_critical_never_shed_and_over_budget_disconnects():
    """CRITICAL pushes evict FLOOD/GOSSIP for room; once nothing
    sheddable remains and the unsheddable backlog would exceed the byte
    budget, the peer is disconnected (ERR_LOAD straggler) rather than
    ever shedding a consensus frame."""
    clock = VirtualClock()
    cap = 4096
    a = make_app(clock, 68, sendq_bytes=cap, stall_ms=60_000)
    b = make_app(clock, 69, sendq_bytes=cap, stall_ms=60_000)
    try:
        conn = congested_pair(clock, a, b)
        peer = conn.initiator
        sq = peer.send_queue
        fill_inflight(a, peer)
        for i in range(10):
            peer.send_message(flood_msg(a, i))
        flood_queued = len(sq._q[CLASS_FLOOD])
        assert flood_queued > 0
        # CRITICAL pushes evict the flood backlog first...
        i = 0
        while len(sq._q[CLASS_FLOOD]) > 0 and i < 100:
            peer.send_message(scp_msg(i))
            i += 1
        assert sq.shed_msgs[CLASS_CRITICAL] == 0
        assert sq.shed_msgs[CLASS_FLOOD] >= flood_queued
        # ...and once the CRITICAL backlog alone exceeds the budget, the
        # peer is dropped as a straggler — never a CRITICAL shed
        while peer.state != PeerState.CLOSING and i < 300:
            peer.send_message(scp_msg(i))
            i += 1
        assert peer.state == PeerState.CLOSING
        assert sq.shed_msgs[CLASS_CRITICAL] == 0
        assert a.overlay_manager.sendq_stats.straggler_disconnects == 1
        assert a.overlay_manager.sendq_stats.shed_msgs[CLASS_CRITICAL] == 0
    finally:
        a.graceful_stop()
        b.graceful_stop()


def test_oversized_unsheddable_frame_delivers_instead_of_disconnecting():
    """REVIEW r17 fix: a single FETCH reply larger than the whole byte
    cap on an otherwise-empty queue must be admitted and delivered (the
    bound becomes max(cap, one frame)) — NOT treated as a straggler.
    Only a genuine unsheddable BACKLOG over the budget disconnects."""
    clock = VirtualClock()
    cap = 1024
    a = make_app(clock, 88, sendq_bytes=cap, stall_ms=60_000)
    b = make_app(clock, 89, sendq_bytes=cap, stall_ms=60_000)
    try:
        conn = authed_pair(clock, a, b)
        peer = conn.initiator
        # a REAL oversized TX_SET reply (the acceptor fully decodes it)
        from stellar_tpu.xdr.ledger import TransactionSet

        txset = TransactionSet(
            previousLedgerHash=b"\x00" * 32,
            txs=[flood_msg(a, 900 + i).value for i in range(30)],
        )
        big = StellarMessage(MessageType.TX_SET, txset)
        body = big.to_xdr()
        assert len(body) > cap  # genuinely over the whole byte budget
        peer.send_message(big, body=body)
        crank(clock)
        # delivered, connection intact, nobody disconnected
        assert peer.state != PeerState.CLOSING
        assert a.overlay_manager.sendq_stats.straggler_disconnects == 0
        assert peer.send_queue.queued_bytes == 0

        # but the SAME frame behind a genuine unsheddable backlog on a
        # congested queue is a straggler disconnect, as before
        conn.initiator.corked = True
        fill_inflight(a, peer)
        for i in range(5):
            peer.send_message(fetch_msg(i))
        assert peer.send_queue.queued_bytes > 0
        peer.send_message(big, body=body)
        assert peer.state == PeerState.CLOSING
        assert a.overlay_manager.sendq_stats.straggler_disconnects == 1
    finally:
        a.graceful_stop()
        b.graceful_stop()


def test_unfittable_flood_frame_sheds_only_itself():
    """REVIEW r17 (second round): a FLOOD frame that can never fit under
    the byte cap — bigger than the cap, or the unsheddable backlog
    leaves no openable room — must NOT evict the live queued backlog
    chasing room that arithmetically cannot exist; the incoming frame is
    the only shed and the connection stays up."""
    clock = VirtualClock()
    cap = 4096
    a = make_app(clock, 93, sendq_bytes=cap, stall_ms=60_000)
    b = make_app(clock, 94, sendq_bytes=cap, stall_ms=60_000)
    try:
        conn = congested_pair(clock, a, b)
        peer = conn.initiator
        sq = peer.send_queue
        fill_inflight(a, peer)
        for i in range(6):
            peer.send_message(flood_msg(a, 600 + i))
        flood_before = len(sq._q[CLASS_FLOOD])
        assert flood_before > 0
        queued_before = sq.queued_bytes
        shed_before = sq.shed_msgs[CLASS_FLOOD]
        huge = StellarMessage(MessageType.TRANSACTION, None)
        ok = sq.enqueue(huge, body=b"\xbb" * (cap + 100))
        assert ok is False  # the unfittable frame itself was the shed
        assert len(sq._q[CLASS_FLOOD]) == flood_before  # backlog intact
        assert sq.queued_bytes == queued_before
        assert sq.shed_msgs[CLASS_FLOOD] == shed_before + 1
        assert peer.state != PeerState.CLOSING
        # even with the FLOOD deque exactly AT its count cap the
        # unfittable frame costs the backlog nothing: the fits check
        # runs before the count-cap shed loop
        sq.max_class_msgs = len(sq._q[CLASS_FLOOD])
        ok = sq.enqueue(huge, body=b"\xbb" * (cap + 100))
        assert ok is False
        assert len(sq._q[CLASS_FLOOD]) == flood_before
        assert sq.queued_bytes == queued_before
        assert sq.shed_msgs[CLASS_FLOOD] == shed_before + 2
    finally:
        a.graceful_stop()
        b.graceful_stop()


# -- straggler stall detection ----------------------------------------------


def test_straggler_stall_disconnect_and_peerrecord_backoff():
    """A CRITICAL frame stuck at the head of a congested queue past
    STRAGGLER_STALL_MS drops the peer with ERR_LOAD — inside the budget
    (virtual-clock timer fires AT the deadline) — and the peer's address
    lands in peerrecord backoff."""
    clock = VirtualClock()
    stall_ms = 700
    a = make_app(clock, 70, sendq_bytes=4096, stall_ms=stall_ms)
    b = make_app(clock, 71, sendq_bytes=4096, stall_ms=stall_ms)
    try:
        conn = congested_pair(clock, a, b)
        peer = conn.initiator
        remote_port = peer.remote_listening_port
        assert remote_port  # learned in the handshake
        fill_inflight(a, peer)
        t0 = clock.now()
        peer.send_message(scp_msg(0))  # CRITICAL, stuck behind inflight
        assert peer.state != PeerState.CLOSING
        crank(clock, n=400, budget=3.0)
        assert peer.state == PeerState.CLOSING
        stats = a.overlay_manager.sendq_stats
        assert stats.straggler_disconnects == 1
        # detection landed INSIDE the budget window
        assert stats.max_stall_ms >= stall_ms
        assert stats.max_stall_ms <= stall_ms + 250
        assert clock.now() - t0 <= (stall_ms / 1000.0) + 0.5
        # ERR_LOAD straggler lands in address-book backoff
        pr = PeerRecord.load(a.database, "127.0.0.1", remote_port)
        assert pr is not None and pr.num_failures >= 1
        assert pr.next_attempt > clock.now()
    finally:
        a.graceful_stop()
        b.graceful_stop()


# -- pack-once fan-out -------------------------------------------------------


def test_broadcast_packs_once_and_shares_the_buffer():
    """Floodgate.broadcast serializes the message ONCE; every peer's
    queue sees the same immutable buffer object (O(1) shed, no
    re-serialization on a wide fan-out) — and the shared-body flood key
    equals the receive path's message_key."""
    clock = VirtualClock()
    a = make_app(clock, 72)
    b = make_app(clock, 73)
    c = make_app(clock, 74)
    try:
        authed_pair(clock, a, b)
        conn_ac = LoopbackPeerConnection(a, c)
        crank(clock)
        assert conn_ac.initiator.is_authenticated()
        peers = a.overlay_manager.authenticated_peers()
        assert len(peers) == 2

        seen_bodies = []
        for p in peers:
            orig = p.send_queue.enqueue

            def hook(msg, body=None, _orig=orig):
                seen_bodies.append(body)
                return _orig(msg, body)

            p.send_queue.enqueue = hook
        msg = flood_msg(a, 1)
        from stellar_tpu.overlay.floodgate import Floodgate

        a.overlay_manager.broadcast_message(msg, force=True)
        assert len(seen_bodies) == 2
        assert seen_bodies[0] is not None
        assert seen_bodies[0] is seen_bodies[1]  # ONE shared buffer
        assert seen_bodies[0] == msg.to_xdr()
        assert Floodgate.message_key(msg, seen_bodies[0]) == (
            Floodgate.message_key(msg)
        )
    finally:
        a.graceful_stop()
        b.graceful_stop()
        c.graceful_stop()


# -- knob off: the reference's unbounded behavior ----------------------------


def test_knob_off_is_passthrough_and_unbounded():
    """OVERLAY_SENDQ_BYTES=0: enqueue degenerates to immediate
    assemble-and-send (no queueing, no shedding, no straggler plane) and
    the loopback transport's legacy depth-1000 shed is back in force."""
    clock = VirtualClock()
    a = make_app(clock, 75, sendq_bytes=0)
    b = make_app(clock, 76, sendq_bytes=0)
    try:
        conn = congested_pair(clock, a, b)
        peer = conn.initiator
        assert not peer.send_queue.active
        n0 = peer.send_mac_seq
        for i in range(MAX_QUEUE_DEPTH + 50):
            peer.send_message(fetch_msg(i))
        # every message hit the transport immediately (seq consumed)...
        assert peer.send_mac_seq == n0 + MAX_QUEUE_DEPTH + 50
        assert peer.send_queue.queued_bytes == 0
        assert peer.send_queue.n_enqueued == 0  # pass-through path
        # ...and the LEGACY transport bound did the (indiscriminate) shed
        assert len(peer.out_queue) == MAX_QUEUE_DEPTH
        assert a.overlay_manager.sendq_stats.straggler_disconnects == 0
        assert sum(a.overlay_manager.sendq_stats.shed_msgs) == 0
    finally:
        a.graceful_stop()
        b.graceful_stop()


def _run_chain(knob_bytes, instance_base):
    """3-node consensus chain to ledger >= 4; returns (hash@4, counters)."""
    from stellar_tpu.crypto.keys import SecretKey
    from stellar_tpu.ledger.headerframe import LedgerHeaderFrame
    from stellar_tpu.simulation import Simulation
    from stellar_tpu.simulation.simulation import OVER_LOOPBACK
    from stellar_tpu.xdr.scp import SCPQuorumSet

    clock = VirtualClock()
    sim = Simulation(OVER_LOOPBACK, clock)
    keys = [SecretKey.pseudo_random_for_testing(i + 1) for i in range(3)]
    qset = SCPQuorumSet(2, [k.get_public_key() for k in keys], [])
    for i, k in enumerate(keys):
        cfg = T.get_test_config(instance_base + i)
        cfg.MANUAL_CLOSE = False
        cfg.OVERLAY_SENDQ_BYTES = knob_bytes
        sim.add_node(k, qset, cfg=cfg)
    for i in range(3):
        for j in range(i + 1, 3):
            sim.add_pending_connection(keys[i], keys[j])
    try:
        sim.start_all_nodes()
        assert sim.crank_until(lambda: sim.have_all_externalized(4), 120)
        assert sim.all_ledgers_agree()
        any_app = next(iter(sim.nodes.values()))
        h = LedgerHeaderFrame.load_by_sequence(any_app.database, 4).get_hash()
        noms = sorted(
            app.herder.n_nomination_rounds for app in sim.nodes.values()
        )
        ballots = sorted(
            app.herder.n_ballot_rounds for app in sim.nodes.values()
        )
        emits = sorted(
            app.herder.m_envelope_emit.count for app in sim.nodes.values()
        )
        return h, (noms, ballots, emits)
    finally:
        sim.stop_all_nodes()
        sim.clock.shutdown()


def test_knob_off_chain_matches_knob_on_bit_exact():
    """The acceptance pin: with the plane ON but uncongested, frames pass
    straight through in enqueue order (same MAC seq, same interleaving),
    so a 3-node consensus chain is bit-identical to the knob-off
    (reference-behavior) run — same ledger hash at the same sequence,
    same SCP round/emission counters."""
    from stellar_tpu.crypto.keys import verify_cache

    verify_cache().clear()
    h_on, counters_on = _run_chain(2 * 1024 * 1024, 80)
    verify_cache().clear()
    h_off, counters_off = _run_chain(0, 84)
    assert h_on == h_off
    assert counters_on == counters_off
