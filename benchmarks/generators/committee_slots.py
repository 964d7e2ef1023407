"""``committee_slots``: a watcher follows a committee, one slot's flood at a
time, on the node's normal path.

The network is keys and a script, not processes: ``core`` validators sharing
one quorum set and ``tier`` validators each with the nested slice ``{2: [self,
{2: core}]}`` (``Topologies::hierarchicalQuorum``).  Each validator emits the
eight statements of ``reference_scp.SEQUENCE`` a slot.  A scripted peer (a
plain ``cpu`` node of the program that holds the committee's quorum sets and
the slots' empty transaction sets, so that it answers ``GET_SCP_QUORUMSET`` and
``GET_TX_SET`` as a node does) delivers a slot's envelopes as ``SCP_MESSAGE``
frames over an authenticated loopback connection, in round order: every
validator's k-th statement before any (k+1)-th, within a round the core first
and the tier in an order drawn from the seed.  They reach the node under test
through ``Peer.recv_message`` -> Floodgate -> ``enqueue_scp_envelope`` -> one
``_flush_scp_batch`` -> herder -> ``PendingEnvelopes`` (which fetches the tx
set and, the first time, the quorum sets from the peer) -> SCP -> externalize
-> close.

A reading is one slot: it starts when the peer's queue is released and ends
when the node has closed the slot's ledger and the flush has handed its last
envelope on; the next slot's flood follows at once.  Its ``items`` are the
envelopes delivered, every verdict counted, forged ones included.  One
envelope in 64 is from a tier author with a corrupted signature; the author's
next statement is valid.

Everything is planned and signed in set-up: the chain of ledger hashes comes
from a plain ``cpu`` node that closes the same empty ledgers first (a value
names the hash of the ledger before it), and the pool is topped up after each
warm-up reading to ``headroom`` times what the window can take at the fastest
slot seen.  A run that exhausts the pool, or whose flood did not arrive as one
flush of the whole slot, fails.

The harness calls no ``verify_batch``, ``recv_scp_envelope`` or
``close_ledger``.  It observes through four thin wrappers (as ``node.Node``
wraps ``close_ledger``): the scheme's ``verify_flush`` (keeps the verdicts),
``PendingEnvelopes.recv_scp_envelope`` (counts forged envelopes that got that
far), ``Herder.value_externalized`` (what was externalized, when, and the
ledger hash) — and, for the controls, the verifier's ``verify``.

Parameters: ``initial_slots`` — slots signed before the first reading;
``headroom`` — pool over need.
"""

from __future__ import annotations

import hashlib
import math
import os
import random
import struct
import time
from typing import Dict, List, Optional

from benchmarks import node as N
from benchmarks import reference_scp as RS
from benchmarks.stats import Reading

STATEMENTS = len(RS.SEQUENCE)
FORGED_ONE_IN = 64
SCP_MESSAGE = 11  # MessageType.SCP_MESSAGE
CLOSE_TIME_BACK_S = 86400  # the closeTime chain starts a day in the past


class Committee:
    """The validators: keys from the seed, quorum sets as plain tuples."""

    def __init__(self, seed: int, n_core: int, n_tier: int):
        self.core = N.keys_from_seed(seed, n_core, b"core validator")
        self.tier = N.keys_from_seed(seed, n_tier, b"tier validator")
        self.keys = {k.public_raw: k for k in self.core + self.tier}
        core = tuple(k.public_raw for k in self.core)
        self.core_qset = (n_core - (n_core - 1) // 3, core, ())
        inner = (2, core, ())
        self.qsets: Dict[bytes, tuple] = {pk: self.core_qset for pk in core}
        for k in self.tier:
            self.qsets[k.public_raw] = (2, (k.public_raw,), (inner,))
        self.qset_hashes = {pk: RS.qset_hash(q) for pk, q in self.qsets.items()}
        self.index = {k.public_raw: i for i, k in enumerate(self.core + self.tier)}
        self.size = len(self.keys)


class SlotPlan:
    """One slot's flood: the two values, the delivery order, which
    statements are forged, every signature and every message body."""

    def __init__(self, slot: int, x: bytes, y: bytes, order: List[bytes], forged: Dict[bytes, tuple]):
        self.slot, self.x, self.y, self.order, self.forged = slot, x, y, order, forged
        self.signatures: List[bytes] = []
        self.bodies: List[bytes] = []
        self.ledger_hash = b""  # the planner's: what closing ``y`` gives

    def deliveries(self, committee: Committee) -> List[RS.Delivery]:
        out, sigs = [], iter(self.signatures)
        for k in range(STATEMENTS):
            for author in self.order:
                st = RS.script_statement(k, committee.qset_hashes[author], self.x, self.y)
                forged = self.forged.get(author, (None,))[0] == k
                out.append(RS.Delivery(author, self.slot, k, st, next(sigs, b""), forged))
        return out


def plan_slot(committee: Committee, seed: int, slot: int, previous_hash: bytes, close_time: int) -> SlotPlan:
    """What the committee says in ``slot``: x and y are the empty transaction
    set on the previous ledger at two close times a second apart."""
    rng = random.Random((seed << 24) ^ slot)
    txh = RS.empty_tx_set_hash(previous_hash)
    x, y = RS.stellar_value(txh, close_time + 1), RS.stellar_value(txh, close_time + 2)
    tier = [k.public_raw for k in committee.tier]
    rng.shuffle(tier)
    n_forged = min(len(tier), committee.size * STATEMENTS // FORGED_ONE_IN)
    # never an author's last statement: its next valid one follows
    forged = {a: (rng.randrange(STATEMENTS - 1), rng.randrange(64), rng.randrange(8)) for a in rng.sample(tier, n_forged)}
    return SlotPlan(slot, x, y, [k.public_raw for k in committee.core] + tier, forged)


def sign_slot(plan: SlotPlan, committee: Committee, network_id: bytes) -> List[bytes]:
    """Sign the slot's statements and pack its messages; returns the forged
    signatures."""
    head = struct.pack(">i", SCP_MESSAGE)
    forged = []
    for d in plan.deliveries(committee):
        sig = committee.keys[d.author].sign(RS.payload(network_id, d))
        if d.forged:
            _, at, bit = plan.forged[d.author]
            sig = sig[:at] + bytes([sig[at] ^ (1 << bit)]) + sig[at + 1:]
            forged.append(sig)
        plan.signatures.append(sig)
        plan.bodies.append(head + RS.pack_envelope(d._replace(signature=sig)))
    return forged


def program_qset(qset: tuple):
    from stellar_tpu.xdr.scp import SCPQuorumSet
    from stellar_tpu.xdr.xtypes import PublicKey

    threshold, validators, inner = qset
    return SCPQuorumSet(threshold, [PublicKey.from_ed25519(v) for v in validators], [program_qset(q) for q in inner])


def plain_config(passphrase: str, work: str, name: str, qset):
    """A ``cpu`` node of the program at its plainest (as
    ``reference.replay_hashes`` builds one): the planner's and the peer's."""
    from stellar_tpu.crypto.keys import SecretKey
    from stellar_tpu.main.config import Config

    cfg = Config()
    cfg.NETWORK_PASSPHRASE = passphrase
    cfg.DATABASE = "sqlite3://:memory:"
    cfg.BUCKET_DIR_PATH = os.path.join(work, name + "-buckets")
    cfg.TMP_DIR_PATH = os.path.join(work, name + "-tmp")
    cfg.RUN_STANDALONE = True
    cfg.MANUAL_CLOSE = True
    cfg.HTTP_PORT = 0
    cfg.SIGNATURE_BACKEND = "cpu"
    cfg.CLOSE_PIPELINE = False
    cfg.INGEST_BATCH = False
    cfg.INVARIANT_CHECKS = []
    cfg.BACKGROUND_BUCKET_MERGE = False
    cfg.OVERLAY_SENDQ_BYTES = 0  # the peer's sends go straight to its transport
    cfg.NODE_SEED = SecretKey.from_seed(hashlib.sha256(b"bench committee " + name.encode()).digest())
    cfg.QUORUM_SET = qset
    return cfg


class Planner:
    """The chain of ledger hashes, from a plain node that closes the slots'
    empty ledgers ahead of the node under test."""

    def __init__(self, passphrase: str, work: str, qset):
        from stellar_tpu.main.application import Application
        from stellar_tpu.util.clock import REAL_TIME, VirtualClock

        self.clock = VirtualClock(REAL_TIME)
        self.app = Application.create(self.clock, plain_config(passphrase, work, "planner", qset), new_db=True)
        self.lm = self.app.ledger_manager
        self.close_time = int(time.time()) - CLOSE_TIME_BACK_S

    @property
    def tip(self) -> bytes:
        return self.lm.last_closed.hash

    @property
    def next_slot(self) -> int:
        return self.lm.last_closed.header.ledgerSeq + 1

    def close(self, plan: SlotPlan) -> None:
        from stellar_tpu.herder.ledgerclose import LedgerCloseData
        from stellar_tpu.herder.txset import TxSetFrame
        from stellar_tpu.xdr.ledger import StellarValue

        value = StellarValue.from_xdr(plan.y)
        self.lm.close_ledger(LedgerCloseData(plan.slot, TxSetFrame(self.tip, []), value))
        self.close_time = value.closeTime
        plan.ledger_hash = self.tip

    def stop(self) -> None:
        self.app.graceful_stop()
        self.clock.shutdown()


class Externalized:
    """What the node externalized in one slot, as it happened."""

    __slots__ = ("slot", "value", "ledger_hash", "history", "recorded")

    def __init__(self, slot, value, history):
        self.slot, self.value, self.history = slot, value, history
        self.ledger_hash = b""  # read once the ledger has closed (the close pipeline closes it later)
        self.recorded = b""  # (author index, k) of each foreign statement SCP recorded


def _statement_k(st) -> int:
    """``reference_scp.statement_k`` over the program's statement."""
    pl = st.pledges
    kind = pl.type.name
    if kind == "SCP_ST_NOMINATE":
        nom = pl.nominate
        return 0 if len(nom.votes) == 1 else (1 if not nom.accepted else 2)
    if kind == "SCP_ST_PREPARE":
        p = pl.prepare
        return 3 if p.prepared is None else (4 if p.nC == 0 else 5)
    return 6 if kind == "SCP_ST_CONFIRM" else 7


class Workload(N.NodeWorkload):
    def __init__(self, ctx):
        from stellar_tpu.main.application import Application
        from stellar_tpu.overlay.loopback import LoopbackPeerConnection
        from stellar_tpu.xdr.overlay import MessageType, StellarMessage

        self.ctx = ctx
        p = ctx.traffic["params"]
        shape = ctx.config["rehearsal"]["committee"] if ctx.rehearsal else ctx.config["committee"]
        self.committee = Committee(ctx.seed, int(shape["core"]), int(shape["tier"]))
        self.per_slot = self.committee.size * STATEMENTS
        self.headroom = float(p["headroom"])

        cfg = N.make_config(ctx.config, ctx.work, ctx.rehearsal, ctx.traffic.get("node"))
        if cfg.NODE_IS_VALIDATOR or cfg.FORCE_SCP:
            raise SystemExit("committee-slots drives a watcher: NODE_IS_VALIDATOR and FORCE_SCP false")
        core_qset = program_qset(self.committee.core_qset)
        cfg.QUORUM_SET = core_qset
        self.node = N.Node(cfg, 0)
        app = self.node.app
        self.network_id = app.network_id
        self.watcher = cfg.NODE_SEED.get_public_key().value
        self.planner = Planner(cfg.NETWORK_PASSPHRASE, ctx.work, core_qset)
        if self.planner.tip != self.node.lm.last_closed.hash:
            raise RuntimeError("the planner's genesis is not the node's")

        # the scripted peer: a plain node that holds what the committee would
        self.peer_app = Application.create(
            self.node.clock, plain_config(cfg.NETWORK_PASSPHRASE, ctx.work, "peer", core_qset), new_db=True
        )
        self.peer_app.start()
        for pk, q in self.committee.qsets.items():
            self.peer_app.herder.recv_scp_quorum_set(self.committee.qset_hashes[pk], program_qset(q))
        app.start()
        conn = LoopbackPeerConnection(self.peer_app, app)
        self.peer, self.acceptor = conn.initiator, conn.acceptor
        self.peer.max_queue_depth = 1 << 30  # the whole slot waits in the transport, nothing shed
        deadline = time.monotonic() + 30.0
        while not (self.peer.is_authenticated() and conn.acceptor.is_authenticated()):
            self.node.clock.crank(False)
            if time.monotonic() > deadline:
                raise RuntimeError("the peer and the node did not authenticate")
        self._msg = StellarMessage(MessageType.SCP_MESSAGE, None)  # the type; each body is packed already

        self.pool: List[SlotPlan] = []
        self.turn = 0
        self.fastest = math.inf
        self.forged_sigs: set = set()
        self._extend(int(p["initial_slots"]))

        self.flushes: list = []  # (envelopes, verdicts as bytes) of every flush, in order
        self.flush_sizes: List[int] = []
        self.forged_in = 0
        self.done: List[Externalized] = []
        self.delivered = 0
        self._after_valid: List[int] = []  # per slot: valid statements in SCP when it externalized
        self._peer_stopped = False
        self._observe()
        self.backend = app.sig_backend.inner  # what the controls break (benchmarks/controls.py)

    # -- set-up -----------------------------------------------------------------
    def _extend(self, n: int) -> None:
        from stellar_tpu.herder.txset import TxSetFrame

        for _ in range(n):
            plan = plan_slot(self.committee, self.ctx.seed, self.planner.next_slot, self.planner.tip,
                             self.planner.close_time)
            txset = TxSetFrame(self.planner.tip, [])
            self.peer_app.herder.recv_tx_set(txset.get_contents_hash(), txset)
            self.planner.close(plan)
            self.forged_sigs.update(sign_slot(plan, self.committee, self.network_id))
            self.pool.append(plan)

    def _top_up(self) -> None:
        """After a warm-up reading: the pool holds ``headroom`` times what the
        window can take at the fastest slot seen, and the readings that may
        still come before it."""
        need = math.ceil(self.headroom * self.ctx.seconds / self.fastest) + 4
        self._extend(max(0, self.turn + need - len(self.pool)))

    def _observe(self) -> None:
        app, herder = self.node.app, self.node.app.herder
        scheme = herder._scheme()
        inner_flush = scheme.verify_flush

        def verify_flush(items, slots):
            out = inner_flush(items, slots)
            self.flushes.append((len(items), bytes(out)))
            return out

        scheme.verify_flush = verify_flush
        pending = herder.pending_envelopes
        inner_recv = pending.recv_scp_envelope

        def recv_scp_envelope(envelope, raw=None):
            if envelope.signature in self.forged_sigs:
                self.forged_in += 1
            return inner_recv(envelope, raw=raw)

        pending.recv_scp_envelope = recv_scp_envelope
        inner_ext = herder.value_externalized

        def value_externalized(slot_index, value):
            slot = herder.scp.known_slots.get(slot_index)
            inner_ext(slot_index, value)
            history = slot.statements_history if slot is not None else []
            self.done.append(Externalized(slot_index, bytes(value), history))

        herder.value_externalized = value_externalized

    # -- one slot -----------------------------------------------------------------
    def step(self, in_window: bool) -> Reading:
        if self.turn >= len(self.pool):
            raise RuntimeError(
                "the pool of signed slots is exhausted after %d: a repeated envelope would be deduplicated and time nothing"
                % len(self.pool)
            )
        plan = self.pool[self.turn]
        self.turn += 1
        node, peer, om = self.node, self.peer, self.node.app.overlay_manager
        flushes, envelopes = om.m_scp_batch_flush.count, om.m_scp_batch_size.count
        before = self._intake()
        peer.corked = True
        send, msg = peer.send_message, self._msg
        for body in plan.bodies:
            send(msg, body=body)
        t0 = time.monotonic()
        peer.set_corked(False)
        deadline = t0 + 600.0
        while node.lm.get_last_closed_ledger_num() < plan.slot or om._scp_batch or om._scp_flush_posted:
            node.clock.crank(False)
            if not (peer.is_authenticated() and self.acceptor.is_authenticated()):
                raise RuntimeError(f"slot {plan.slot}: the connection between the peer and the node was dropped")
            if time.monotonic() > deadline:
                raise RuntimeError(f"the node did not close slot {plan.slot} in 600 s")
        t1 = time.monotonic()
        self.delivered += len(plan.bodies)
        node.settle()
        flushes = om.m_scp_batch_flush.count - flushes
        envelopes = om.m_scp_batch_size.count - envelopes
        self.flush_sizes.append(envelopes if flushes == 1 else -flushes)
        if flushes != 1 or envelopes != len(plan.bodies):
            raise RuntimeError(
                f"slot {plan.slot}: the flood of {len(plan.bodies)} arrived as {flushes} flush(es) of {envelopes} in all"
            )
        self._settle_history()
        after = self._intake()
        if after is not None:
            self.ctx.span("bench.scp_slot", t0, t1, slot=plan.slot, **{k: after[k] - before[k] for k in after})
        if not in_window:
            self.fastest = min(self.fastest, t1 - t0)
            self._top_up()
        return Reading(t0, t1, len(plan.bodies))

    def _intake(self) -> Optional[dict]:
        stats = getattr(self.node.app.herder, "scp_stats", None)
        if stats is None:  # a program without the counters
            return None
        s = stats()
        return {k: s[k] for k in ("to_scp", "quorum_checks", "quorum_nodes_scanned", "payload_encodes")}

    def _settle_history(self) -> None:
        """Between readings: what SCP recorded of the slots just externalized,
        as (author, k) pairs, and let go of the statements."""
        index = self.committee.index
        lcl = self.node.lm.last_closed
        for e in self.done:
            if e.history is None:
                continue
            if e.slot == lcl.header.ledgerSeq:
                # with the close pipeline on the ledger closes after
                # value_externalized has returned: read its hash now
                e.ledger_hash = lcl.hash
            out = bytearray()
            for st in e.history:
                author = st.nodeID.value
                if author != self.watcher:
                    out += struct.pack(">HB", index.get(author, 0xFFFF), _statement_k(st))
            e.recorded, e.history = bytes(out), None

    # -- what the layer metrics read ------------------------------------------------
    def counters(self) -> dict:
        out = self.node.counters()
        stats = getattr(self.node.app.herder, "scp_stats", None)
        if stats is not None:
            out["scp"] = stats()
        return out

    def drain_spans(self) -> list:
        spans = super().drain_spans()
        repeat = self.ctx.span
        for s in spans:
            a = s.attrs
            if not a:
                continue
            if s.name == "overlay.scp_flush" and "envelopes" in a:
                repeat("bench.scp_flush", s.end, s.end, envelopes=a["envelopes"], rejected=a["rejected"])
            elif s.name in ("scp.deliver", "herder.recheck") and "receive_s" in a:
                repeat("bench.scp_intake", s.end, s.end, seconds=s.end - s.start, to_scp=a["to_scp"],
                       dropped_window=a["dropped_window"], receive_s=a["receive_s"], close_s=a["close_s"])
        return spans

    def notes(self) -> dict:
        return {
            "validators": self.committee.size, "statements_per_validator": STATEMENTS,
            "envelopes_per_slot": self.per_slot, "forged_per_slot": len(self.pool[0].forged),
            "quorum_sets": len(set(self.committee.qset_hashes.values())),
            "slots_signed": len(self.pool), "slots_delivered": self.turn,
            "flush_sizes": self.flush_sizes,
            "externalized_after_valid": self._after_valid,
            "fastest_warmup_slot_s": self.fastest,
        }

    # -- the comparison ---------------------------------------------------------------
    def finish(self) -> None:
        from benchmarks import reference as ref

        node = self.node
        self._at_close = (
            node.lm.last_closed.header.ledgerSeq, node.lm.last_closed.hash.hex(),
            ref.durable_state(self.db_path(), balances=False),
        )

    def close(self) -> None:
        if not self._peer_stopped:
            self._peer_stopped = True
            self.peer_app.graceful_stop()
            self.planner.stop()
        super().close()

    def check(self, check) -> tuple:
        """Every slot delivered, held to ``reference_scp`` on what the timed
        path itself produced; every limit 0."""
        node, committee = self.node, self.committee
        self._settle_history()
        inv = node.app.invariants.dump_info()
        check.compare("invariant_violations", int(inv.get("total_violations", 0)), 0)
        check.compare("closes_not_invariant_checked", max(0, self.turn - int(inv.get("closes_checked", 0))), 0)
        lcl_seq, lcl_hash, then = self._at_close
        check.compare("durable_lcl_seq_behind", lcl_seq - (then["top"] or 0), 0, "as the last timed slot closed")
        check.compare("durable_lcl_hash_differs", 0 if then["lcl"] == lcl_hash else 1, 0, f"lcl {lcl_seq}")

        done = {e.slot: e for e in self.done}
        bad_verdicts = value_differs = early = missing = off = bad_hash = 0
        forged = 0
        self._after_valid = []
        for i, plan in enumerate(self.pool[: self.turn]):
            deliveries = plan.deliveries(committee)
            forged += sum(1 for d in deliveries if d.forged)
            want = RS.verdicts(self.network_id, deliveries)
            n, got = self.flushes[i] if i < len(self.flushes) else (0, b"")
            bad_verdicts += abs(len(want) - n) + sum(1 for g, w in zip(got, want) if bool(g) != w)
            outcome = RS.slot_outcome(deliveries, want, committee.core_qset, committee.qsets)
            e = done.get(plan.slot)
            if e is None:
                missing += 1
                continue
            value_differs += e.value != outcome.value
            bad_hash += e.ledger_hash != plan.ledger_hash
            recorded = len(e.recorded) // 3
            self._after_valid.append(recorded)
            early += outcome.valid_before is None or recorded < outcome.valid_before
            valid = [d for d, ok in zip(deliveries, want) if ok][:recorded]
            expect = b"".join(struct.pack(">HB", committee.index[d.author], d.k) for d in valid)
            off += abs(recorded - len(valid)) + sum(
                1 for j in range(0, min(len(expect), len(e.recorded)), 3) if expect[j:j + 3] != e.recorded[j:j + 3]
            )
        check.compare("verdicts_differing", bad_verdicts, 0, f"of {self.delivered} against libsodium, {forged} forged")
        check.compare("slots_value_differs", value_differs, 0, f"of {self.turn} slots")
        check.compare("slots_externalized_early", early, 0)
        check.compare("slots_not_externalized", missing + max(0, len(self.done) - self.turn), 0)
        check.compare("forged_reaching_scp", self.forged_in, 0, "past the herder, into PendingEnvelopes")
        check.compare("statements_off", off, 0, "recorded by SCP against the valid ones the reference lets through")
        check.compare("ledger_hashes_differing", bad_hash, 0, "against the planner's plain cpu node")
        failed = bad_verdicts + value_differs + early + missing + self.forged_in + off + bad_hash
        return self.delivered, failed
