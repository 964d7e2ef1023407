"""A watcher follows a hierarchical committee (ISSUE 32, ``scp4096``), at
small sizes on the CPU: the node against the plain federated-voting
reference (``tests/reference_scp.py``), through the benchmark's own generator
and so through the node's normal path (peer message -> Floodgate -> the
overlay's flush -> herder -> ``PendingEnvelopes`` -> SCP -> close).

- the reference shares nothing with the program, its benchmark copy is the
  same file, and its hand packing is the program's XDR byte for byte;
- quorum slices, v-blocking sets and quorums by plain set arithmetic;
- the script's statements are what a ``core(4)`` simulation emits, plus the
  one pad the configuration's file names;
- ``test_node_follows_the_committee``: 4 + 12 validators on both backends
  (``tpu`` over XLA/CPU at cutover 0) and 4 + 60 once: the value, when, what
  SCP recorded (statement for statement, as XDR), forged envelopes, ledger
  hashes, the same verdict list from both backends;
- the spans, the ``/info`` ``scp`` block, the per-caller device share;
- a peer is not dropped for idleness because the node itself was held.
"""

import ast
import copy
import os
import shutil

import pytest
import reference_scp as RS

from benchmarks.generators import committee_slots as CS
from benchmarks.measure import Ctx, load_json
from benchmarks.reference import Check

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2147483951
SLOTS = 3


# -- the reference itself -----------------------------------------------------


def test_reference_copy_is_identical():
    with open(os.path.join(ROOT, "tests", "reference_scp.py"), "rb") as a:
        with open(os.path.join(ROOT, "benchmarks", "reference_scp.py"), "rb") as b:
            assert a.read() == b.read()


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "tests", "reference_scp.py")) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or "").split(".")[0])
    assert names == {"__future__", "ctypes", "hashlib", "struct", "typing"}


def _program_statement(d):
    import stellar_tpu.xdr.scp as X
    from stellar_tpu.xdr.xtypes import PublicKey

    ballot = lambda b: None if b is None else X.SCPBallot(b[0], b[1])  # noqa: E731
    st, T = d.statement, X.SCPStatementType
    if st[0] == "NOMINATE":
        pledges = X.SCPStatementPledges(T.SCP_ST_NOMINATE, X.SCPNomination(st[1], list(st[2]), list(st[3])))
    elif st[0] == "PREPARE":
        pledges = X.SCPStatementPledges(
            T.SCP_ST_PREPARE, X.SCPStatementPrepare(st[1], ballot(st[2]), ballot(st[3]), ballot(st[4]), st[5], st[6])
        )
    elif st[0] == "CONFIRM":
        pledges = X.SCPStatementPledges(T.SCP_ST_CONFIRM, X.SCPStatementConfirm(st[1], st[2], ballot(st[3]), st[4]))
    else:
        pledges = X.SCPStatementPledges(T.SCP_ST_EXTERNALIZE, X.SCPStatementExternalize(ballot(st[1]), st[2], st[3]))
    return X.SCPStatement(PublicKey.from_ed25519(d.author), d.slot, pledges)


@pytest.mark.parametrize("k", range(len(RS.SEQUENCE)))
def test_hand_packing_is_the_programs_xdr(k):
    from stellar_tpu.crypto import sha256
    from stellar_tpu.herder.txset import TxSetFrame
    from stellar_tpu.scp import quorum
    from stellar_tpu.xdr.base import xdr_to_opaque
    from stellar_tpu.xdr.entries import EnvelopeType
    from stellar_tpu.xdr.ledger import StellarValue
    from stellar_tpu.xdr.scp import SCPEnvelope

    committee = CS.Committee(SEED, 4, 3)
    network_id, previous = sha256(b"a network"), sha256(b"a ledger")
    plan = CS.plan_slot(committee, SEED, 7, previous, 1000)
    CS.sign_slot(plan, committee, network_id)
    assert plan.x == StellarValue(RS.empty_tx_set_hash(previous), 1001, [], 0).to_xdr()
    assert RS.empty_tx_set_hash(previous) == TxSetFrame(previous, []).get_contents_hash()
    for d in (d for d in plan.deliveries(committee) if d.k == k):
        st = _program_statement(d)
        assert RS.pack_envelope(d) == SCPEnvelope(st, d.signature).to_xdr()
        assert RS.payload(network_id, d) == xdr_to_opaque(network_id, EnvelopeType.ENVELOPE_TYPE_SCP, st)
        assert RS.statement_k(d.statement) == CS._statement_k(st) == k
        q = committee.qsets[d.author]
        assert RS.qset_hash(q) == quorum.qset_hash(CS.program_qset(q))


CORE = (3, (b"a", b"b", b"c", b"d"), ())
TIER = (2, (b"t",), ((2, (b"a", b"b", b"c", b"d"), ()),))


@pytest.mark.parametrize(
    "qset, nodes, slice_, blocking",
    [
        (CORE, {b"a", b"b"}, False, True),
        (CORE, {b"a", b"b", b"c"}, True, True),
        (CORE, {b"a"}, False, False),
        (CORE, {b"t", b"a"}, False, False),
        (TIER, {b"t", b"a", b"b"}, True, True),
        (TIER, {b"a", b"b", b"c"}, False, True),  # three of the core block the inner set, so the slice
        (TIER, {b"a", b"b"}, False, False),
        (TIER, {b"t"}, False, True),
        ((0, (), ()), set(), True, False),
    ],
)
def test_slices_and_blocking_sets(qset, nodes, slice_, blocking):
    from stellar_tpu.scp import quorum
    from stellar_tpu.xdr.xtypes import PublicKey

    assert RS.is_slice(qset, nodes) is slice_
    assert RS.is_v_blocking(qset, nodes) is blocking
    if qset[0]:  # the program's arithmetic agrees (32-byte keys)
        wide = lambda q: (q[0], tuple(v.ljust(32, b"\0") for v in q[1]), tuple(wide(i) for i in q[2]))  # noqa: E731
        have = {PublicKey.from_ed25519(n.ljust(32, b"\0")) for n in nodes}
        assert quorum.is_quorum_slice(CS.program_qset(wide(qset)), have) is slice_
        assert quorum.is_v_blocking(CS.program_qset(wide(qset)), have) is blocking


def test_quorums_and_the_transitive_quorum():
    qsets = {n: CORE for n in CORE[1]}
    qsets[b"t"] = TIER
    assert RS.quorum_within({b"a", b"b", b"t"}, qsets) == set()
    assert RS.quorum_within({b"a", b"b", b"c", b"t"}, qsets) == {b"a", b"b", b"c", b"t"}
    assert RS.quorum_within({b"a", b"b", b"c", b"u"}, qsets) == {b"a", b"b", b"c"}
    assert RS.transitive_quorum(CORE, qsets) == set(CORE[1])
    assert RS.transitive_quorum(TIER, qsets) == set(CORE[1]) | {b"t"}


def test_outcome_is_the_third_core_confirm():
    committee = CS.Committee(SEED, 4, 12)
    plan = CS.plan_slot(committee, SEED, 2, b"\1" * 32, 50)
    deliveries = plan.deliveries(committee)
    ok = [not d.forged for d in deliveries]
    out = RS.slot_outcome(deliveries, ok, committee.core_qset, committee.qsets)
    assert out.value == plan.y and out.index == 6 * committee.size + 2
    assert out.valid_before == sum(ok[: out.index + 1])
    # a core CONFIRM refused: the fourth core node's completes the quorum
    ok[6 * committee.size] = False
    assert RS.slot_outcome(deliveries, ok, committee.core_qset, committee.qsets).index == 6 * committee.size + 3
    # two refused: no quorum of the core until they externalize
    ok[6 * committee.size + 1] = False
    assert RS.slot_outcome(deliveries, ok, committee.core_qset, committee.qsets).index == 7 * committee.size + 0


def test_script_is_what_a_core4_simulation_emits():
    """The configuration lists eight statements; seven are what an honest
    node of this program emits in a slot of one round, recorded here, and the
    pad (the second line) adds the value y."""
    from stellar_tpu.herder.herder import Herder
    from stellar_tpu.simulation import topologies

    emitted = {}
    inner = Herder.emit_envelope

    def emit(self, envelope):
        emitted.setdefault((self.secret_key.public_raw, envelope.statement.slotIndex), []).append(envelope.statement)
        return inner(self, envelope)

    Herder.emit_envelope = emit
    sim = topologies.core(4)
    try:
        sim.start_all_nodes()
        assert sim.crank_until(lambda: sim.have_all_externalized(3), 120)
    finally:
        Herder.emit_envelope = inner
        sim.stop_all_nodes()
        sim.clock.shutdown()

    def shape(st):
        pl, T = st.pledges, st.pledges.type.name
        if T == "SCP_ST_NOMINATE":
            return ("NOMINATE", len(pl.nominate.votes), len(pl.nominate.accepted))
        if T == "SCP_ST_PREPARE":
            p = pl.prepare
            counters = tuple(None if b is None else b.counter for b in (p.ballot, p.prepared, p.preparedPrime))
            return ("PREPARE",) + counters + (p.nC, p.nP)
        if T == "SCP_ST_CONFIRM":
            return ("CONFIRM", pl.confirm.nPrepared, pl.confirm.commit.counter, pl.confirm.nP)
        return ("EXTERNALIZE", pl.externalize.commit.counter, pl.externalize.nP)

    def script_shape(st):
        if st[0] == "NOMINATE":  # the pad's value y left out
            return ("NOMINATE", len(st[2]) - (len(st[2]) > 1), len(st[3]) - (len(st[3]) > 1))
        if st[0] == "PREPARE":
            return ("PREPARE",) + tuple(None if b is None else b[0] for b in st[2:5]) + st[5:7]
        if st[0] == "CONFIRM":
            return ("CONFIRM", st[2], st[3][0], st[4])
        return ("EXTERNALIZE", st[1][0], st[2])

    recorded = [[shape(st) for st in sts] for (_, slot), sts in emitted.items() if slot == 3]
    assert len(recorded) == 4 and all(r == recorded[0] for r in recorded)
    script = [script_shape(RS.script_statement(k, b"q" * 32, b"x", b"y")) for k in range(8)]
    assert script[0] == script[1]  # the pad repeats the first vote with y beside it
    assert script[:1] + script[2:] == recorded[0]
    config = load_json(os.path.join(ROOT, "benchmarks", "configs", "scp4096.json"))
    listed = config["statements_per_validator_per_slot"]
    assert [l.split(" (")[0] for l in listed] == list(RS.SEQUENCE) and "pad" in listed[1]


# -- the node against the reference ----------------------------------------------


class Kept(CS.Workload):
    """The benchmark's workload, keeping what SCP recorded as XDR."""

    kept: dict

    def _settle_history(self):
        for e in self.done:
            if e.history is not None:
                self.kept[e.slot] = [st.to_xdr() for st in e.history if st.nodeID.value != self.watcher]
        super()._settle_history()


def _drive(tmp, backend: str, tier: int, control=None):
    from stellar_tpu.crypto.keys import PubKeyUtils

    PubKeyUtils.clear_verify_sig_cache()
    config = copy.deepcopy(load_json(os.path.join(ROOT, "benchmarks", "configs", "scp4096.json")))
    config["rehearsal"]["committee"] = {"core": 4, "tier": tier}
    config["rehearsal"]["node"] = {"SIGNATURE_BACKEND": backend, "TPU_CPU_CUTOVER": 0, "SIG_BATCH_MAX": 32}
    traffic = load_json(os.path.join(ROOT, "benchmarks", "traffic", "committee-slots.json"))
    work = str(tmp / f"{backend}-{tier}")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    ctx = Ctx(seed=SEED, config=config, traffic=traffic, cell={"name": "scp4096.envelopes"}, work=work,
              rehearsal=True, root=ROOT, seconds=0.01)
    Kept.kept = {}
    wl = Kept(ctx)
    try:
        if control:
            from benchmarks import controls

            controls.apply(control, wl)
        before = wl.counters()
        spans = []
        for _ in range(SLOTS):
            wl.step(True)
            spans.extend(wl.drain_spans())
        wl.finish()
        check = Check()
        attempted, failed = wl.check(check)
        return {
            "rows": {r["name"]: r["value"] for r in check.rows}, "attempted": attempted, "failed": failed,
            "verdicts": [v for _, v in wl.flushes], "notes": wl.notes(), "kept": dict(Kept.kept),
            "plans": wl.pool[:SLOTS], "committee": wl.committee, "network_id": wl.network_id,
            "spans": spans, "bench_spans": list(ctx.spans), "before": before, "after": wl.counters(),
            "values": [e.value for e in wl.done],
        }
    finally:
        wl.close()


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("committee")
    cache = {}

    def get(backend, tier):
        if (backend, tier) not in cache:
            cache[backend, tier] = _drive(tmp, backend, tier)
        return cache[backend, tier]

    return get


@pytest.mark.parametrize("backend, tier", [("cpu", 12), ("tpu", 12), ("cpu", 60)])
def test_node_follows_the_committee(runs, backend, tier):
    r = runs(backend, tier)
    assert r["failed"] == 0 and all(v == 0 for v in r["rows"].values()), r["rows"]
    committee, size = r["committee"], 4 + tier
    assert r["attempted"] == SLOTS * size * 8
    assert r["notes"]["flush_sizes"] == [size * 8] * SLOTS
    for plan, value, after in zip(r["plans"], r["values"], r["notes"]["externalized_after_valid"]):
        deliveries = plan.deliveries(committee)
        ok = RS.verdicts(r["network_id"], deliveries)
        assert [d.forged for d in deliveries] == [not v for v in ok] and ok.count(False) == size * 8 // 64
        out = RS.slot_outcome(deliveries, ok, committee.core_qset, committee.qsets)
        # exactly the reference's value, exactly at the reference's delivery
        assert value == out.value == plan.y and after == out.valid_before
        valid = [d for d, v in zip(deliveries, ok) if v][:after]
        assert r["kept"][plan.slot] == [RS.pack_statement(d.author, d.slot, d.statement) for d in valid]


def test_both_backends_give_the_same_verdicts(runs):
    cpu, tpu = runs("cpu", 12), runs("tpu", 12)
    assert cpu["verdicts"] == tpu["verdicts"] and len(cpu["verdicts"]) == SLOTS
    assert tpu["after"]["sig_backend"]["caller_items"]["overlay"] == {"device": SLOTS * 128, "host": 0}


@pytest.mark.parametrize("control, row", [("accept-invalid", "forged_reaching_scp"), ("refuse-valid", "verdicts_differing")])
def test_a_broken_verifier_is_not_correct(tmp_path, control, row):
    """``accept-invalid``: the batch's verdict is latched in the verify
    cache, so the herder's own check passes the forged envelope on to SCP;
    ``refuse-valid``: a core validator's statement is dropped at the flush."""
    r = _drive(tmp_path, "tpu", 12, control=control)
    assert r["failed"] >= 1 and r["rows"]["verdicts_differing"] == SLOTS and r["rows"][row] >= 1


def test_spans_and_counters(runs):
    r = runs("cpu", 12)
    by = {}
    for s in r["spans"]:
        by.setdefault(s.name, []).append(s)
    flushes = by["overlay.scp_flush"]
    assert [s.attrs["envelopes"] for s in flushes] == [128] * SLOTS and all(s.attrs["rejected"] == 2 for s in flushes)
    assert len(by["scp.collect"]) == len(by["scp.deliver"]) == SLOTS
    for name in ("scp.collect", "sig.flush", "scp.deliver"):
        assert all(any(s.parent == f.sid for f in flushes) for s in by[name]), name
    sampled = by["herder.recv_envelope"]
    assert all(s.attrs["index"] % 64 == 0 for s in sampled) and 1 <= len(sampled) <= 2 * SLOTS
    assert by["scp.receive"] and by["herder.recheck"]
    intake = by["scp.deliver"] + by["herder.recheck"]
    scp = {k: r["after"]["scp"][k] - r["before"]["scp"][k] for k in r["after"]["scp"]}
    assert sum(s.attrs["to_scp"] for s in intake) == scp["to_scp"] == sum(r["notes"]["externalized_after_valid"])
    assert sum(s.attrs["dropped_window"] for s in intake) == scp["dropped_out_of_window"] == 0
    assert abs(sum(s.attrs["receive_s"] for s in intake) - scp["receive_s"]) < 1e-3
    assert scp["envelopes_flushed"] == 128 * SLOTS and scp["rejected_at_flush"] == 2 * SLOTS
    assert scp["quorum_checks"] > 0 and scp["quorum_nodes_scanned"] > scp["quorum_checks"]
    # the flush's triple, the herder's gate and SCP's own check: three an envelope that gets that far
    assert 2 * scp["to_scp"] < scp["payload_encodes"] <= 3 * 128 * SLOTS
    repeats = {s.name for s in r["bench_spans"]}
    assert {"bench.scp_flush", "bench.scp_intake", "bench.scp_slot"} <= repeats


def test_info_has_the_scp_block(tmp_path):
    from stellar_tpu.main.application import Application
    from stellar_tpu.main.commandhandler import CommandHandler
    from stellar_tpu.tx import testutils as T
    from stellar_tpu.util import VirtualClock

    clock = VirtualClock()
    app = Application.create(clock, T.get_test_config(31), new_db=True)
    try:
        scp = CommandHandler(app).handle_info({})["info"]["scp"]
        assert set(scp) == {
            "envelopes_flushed", "rejected_at_flush", "to_scp", "dropped_out_of_window", "quorum_checks",
            "quorum_nodes_scanned", "payload_encodes", "receive_s", "close_s",
        }
    finally:
        app.graceful_stop()
        clock.shutdown()


def test_a_held_node_does_not_drop_its_peers_for_idleness():
    """A bucket's first dispatch holds the main thread for longer than the
    30 s idle timeout; the silence is the node's own.  A peer that stays
    silent through a whole window after that is still dropped."""
    from stellar_tpu.main.application import Application
    from stellar_tpu.overlay.loopback import LoopbackPeerConnection
    from stellar_tpu.overlay.peer import PeerState
    from stellar_tpu.tx import testutils as T
    from stellar_tpu.util import VirtualClock

    clock = VirtualClock()
    apps = []
    for i in (32, 33):
        cfg = T.get_test_config(i)
        cfg.RUN_STANDALONE, cfg.HTTP_PORT = True, 0
        apps.append(Application.create(clock, cfg, new_db=True))
        apps[-1].start()
    try:
        conn = LoopbackPeerConnection(*apps)
        assert clock.crank_until(lambda: conn.initiator.is_authenticated() and conn.acceptor.is_authenticated(), 5)
        def held():
            clock._virtual_now += 70.0  # the main thread comes back 70 s later

        clock.post(held)
        clock.crank()
        clock.crank()
        assert conn.initiator.state != PeerState.CLOSING and conn.acceptor.state != PeerState.CLOSING
        assert clock.crank_until(lambda: conn.acceptor.state == PeerState.CLOSING, 40)
    finally:
        for a in apps:
            a.graceful_stop()
        clock.shutdown()
