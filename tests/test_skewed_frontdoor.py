"""The front door under skewed accounts (ISSUE 49, ``zipf1000``), at width 24
over 240 accounts on the CPU: a dozen ledgers whose sets carry per-account
sequence chains, through the benchmark's own generator
(``benchmarks/generators/skewed_backlog.py``) and so through the node's normal
path — ``IngestPlane.submit_sync`` -> the herder's queue -> trim, surge filter,
chain walk -> SCP -> close.

One world a seed: the cell's whole check (ledger hashes against the plain
``cpu`` node, balances, and the plain reference ``tests/reference_skew.py``,
which shares nothing with the program: sequence numbers, the protocol's apply
order, gapless per-account prefixes, the shape), the herder's ``tx_queue``
counters and the new span attributes against counts taken from the stream, a
chain the surge filter cut in the middle, the ``chain-order`` control, and the
band that tells a Zipf draw from a uniform one.
"""

import ast
import copy
import os
import random

import pytest
import reference_skew as RS

from benchmarks import spans as SP
from benchmarks.generators import skewed_backlog as SB
from benchmarks.measure import Ctx, load_json
from benchmarks.reference import Check
from benchmarks.tools import chain_order

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = (49, 4901, 2147489949)
LEDGERS = 12
CHECK_ROWS = (
    "submissions_refused", "left_pending_after_drain", "invariant_violations", "closes_not_invariant_checked",
    "durable_lcl_seq_behind", "durable_lcl_hash_differs", "closed_txs_not_yet_in_txhistory", "txs_not_in_txhistory",
    "ledger_hashes_differing", "balances_off_plain_arithmetic",
) + RS.ROWS
READERS = (
    "longest_chain_per_ledger", "source_accounts_per_ledger", "surge_cut_txs_per_ledger",
    "queue_build_ms_per_ledger", "sort_for_apply_ms_per_ledger", "apply_batches_per_ledger",
)


def make_ctx(work: str, seed: int, node: dict = None) -> Ctx:
    config = load_json(os.path.join(ROOT, "benchmarks", "configs", "zipf1000.json"))
    traffic = copy.deepcopy(load_json(os.path.join(ROOT, "benchmarks", "traffic", "skewed-backlog.json")))
    traffic["node"].update(node or {})
    return Ctx(seed=seed, config=config, traffic=traffic, cell=None, work=work, rehearsal=True, root=ROOT, seconds=1.0)


def chains(txs) -> dict:
    """source -> its transactions' sequence numbers, ascending."""
    out = {}
    for t in txs:
        out.setdefault(t.source, []).append(t.seq)
    return {k: sorted(v) for k, v in out.items()}


class Ledger:
    """One cycle, as the stream and the closed set tell it."""


def drive(wl, ledgers: int) -> list:
    out, pending = [], []
    for _ in range(ledgers):
        led = Ledger()
        at = wl.cursor
        led.reading = wl.step(True)
        offered = [RS.parse(b) for b in wl.stream[at : wl.cursor]]
        # admitted behind a pending transaction of the same account
        waiting = {t.source for t in pending}
        led.chained = 0
        for t in offered:
            led.chained += t.source in waiting
            waiting.add(t.source)
        led.at_trigger = pending + offered
        led.seq = wl.node.closed[-1].seq
        led.closed = [RS.parse(b) for b in wl.node.closed[-1].envelopes]
        gone = {t.full_hash for t in led.closed}
        pending = led.left = [t for t in led.at_trigger if t.full_hash not in gone]
        led.stats = wl.herder.tx_queue_stats()
        led.spans = wl.drain_spans()
        out.append(led)
    return out


@pytest.fixture(scope="module", params=SEEDS)
def world(request, tmp_path_factory):
    w = Ledger()
    w.seed = request.param
    wl = w.wl = SB.Workload(make_ctx(str(tmp_path_factory.mktemp("skew")), w.seed))
    try:
        w.before = wl.counters()
        wl.drain_spans()  # the funding ledgers'
        wl.ctx.spans.clear()
        w.ledgers = drive(wl, LEDGERS)
        w.after = wl.counters()
        w.bench_spans = list(wl.ctx.spans)
        w.info = wl.node.app.command_handler.handle_info({})["info"]
        wl.finish()
        check = Check()
        w.attempted, w.failed = wl.check(check)
        w.rows = {r["name"]: r for r in check.rows}
        w.stored = RS.read_ledgers(wl.db_path())[0]
        yield w
    finally:
        wl.close()


# -- the reference itself ---------------------------------------------------------


def test_reference_copy_is_identical():
    with open(os.path.join(ROOT, "tests", "reference_skew.py"), "rb") as a:
        with open(os.path.join(ROOT, "benchmarks", "reference_skew.py"), "rb") as b:
            assert a.read() == b.read()


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "tests", "reference_skew.py")) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or "").split(".")[0])
    assert names == {"__future__", "base64", "hashlib", "math", "sqlite3", "statistics", "struct", "typing"}


def _random_set(n_accounts: int, n_txs: int, seed: int):
    """A set with chains, as program frames, on a network of its own."""
    from stellar_tpu.crypto import sha256
    from benchmarks import node as N

    rng = random.Random(seed)
    keys = N.keys_from_seed(seed, n_accounts)
    seqs = [(7 << 32) + 1] * n_accounts
    frames = []
    for _ in range(n_txs):
        s = min(int(rng.paretovariate(1.0)) - 1, n_accounts - 1)
        d = (s + 1 + rng.randrange(n_accounts - 1)) % n_accounts
        frames.append(N.tx_frame(sha256(b"a network"), 100, keys[s], seqs[s], [N.payment_op(keys[d], 1000)]))
        seqs[s] += 1
    rng.shuffle(frames)
    return frames


@pytest.mark.parametrize("seed", range(4))
def test_plain_parse_and_apply_order_are_the_programs(seed):
    from stellar_tpu.crypto import sha256
    from stellar_tpu.herder.txset import TxSetFrame

    frames = _random_set(9, 60, seed)
    previous = sha256(b"a ledger %d" % seed)
    txset = TxSetFrame(previous, frames)
    plain = [RS.parse(f.envelope.to_xdr()) for f in frames]
    for f, t in zip(frames, plain):
        assert (t.source, t.fee, t.seq, t.full_hash) == (f.source_bytes(), f.get_fee(), f.get_seq_num(), f.get_full_hash())
        op = f.envelope.tx.operations[0].body.value
        assert t.ops == (("pay", op.destination.value, op.amount),)
    tally = {}
    want = [f.get_full_hash() for f in txset.sort_for_apply(tally)]
    assert [t.full_hash for t in RS.apply_order(plain, previous)] == want
    by = chains(plain)
    assert tally == {"accounts": len(by), "batches": max(map(len, by.values()))}
    assert tally["batches"] > 4  # more than sort_for_apply's first four batches
    # the control's order is another one, and keeps every account's sequence
    broken = chain_order.hash_order_alone(txset)
    assert [f.get_full_hash() for f in broken] != want and sorted(map(id, broken)) == sorted(map(id, frames))
    for src, seqs in chains([RS.parse(f.envelope.to_xdr()) for f in broken]).items():
        assert [f.get_seq_num() for f in broken if f.source_bytes() == src] == seqs


def test_plain_accounts_by_hand():
    a, b, c = (bytes([i]) * 32 for i in (1, 2, 3))
    tx = lambda src, fee, seq, *ops: RS.Tx(src, fee, seq, ops, b"", b"")  # noqa: E731
    ledgers = {
        2: [tx(a, 200, 1, ("create", b, 5000), ("create", c, 7000))],
        5: [tx(b, 100, (2 << 32) + 1, ("pay", c, 30)), tx(b, 100, (2 << 32) + 2, ("pay", a, 5))],
    }
    assert RS.plain_accounts(ledgers, {a: (10**6, 0)}) == {
        a: [10**6 - 200 - 12000 + 5, 1], b: [5000 - 200 - 35, (2 << 32) + 2], c: [7030, 2 << 32],
    }


def test_chain_gaps_by_hand():
    key = lambda i: bytes(4) + bytes([i]) * 32  # noqa: E731 - a blob's first 36 bytes
    blob = lambda i, n: key(i) + bytes([n])  # noqa: E731
    tx = lambda i, n: RS.Tx(key(i)[4:], 0, n, (("pay", b"", 0),), b"", blob(i, n))  # noqa: E731
    stream = [blob(1, 1), blob(2, 1), blob(1, 2), blob(1, 3)]
    assert RS.chain_gaps({3: [tx(1, 1), tx(2, 1)], 4: [tx(1, 2)]}, stream) == (0, 2)
    assert RS.chain_gaps({3: [tx(1, 1)], 4: [tx(1, 3)]}, stream) == (1, 1)  # a gap
    assert RS.chain_gaps({3: [tx(1, 2), tx(1, 1)]}, stream) == (1, 1)  # out of order
    assert RS.chain_gaps({3: [tx(3, 1)]}, stream) == (1, 1)  # never offered


# -- the shape: a Zipf draw inside its band, a uniform one outside ---------------------


def test_the_distribution_is_the_issues():
    top, top_pct = RS.top_shares(10000, 0.99)
    assert round(100 * top, 1) == 9.8 and round(100 * top_pct, 1) == 51.8
    assert RS.hot_ranks(10000) == 100 and RS.hot_ranks(240) == 2 and RS.hot_ranks(50) == 1


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("n, draws, backlog", [(240, 1200, 0), (240, 1200, 48), (10000, 50000, 2000)])
def test_a_zipf_draws_top_share_is_inside_its_band_and_a_uniform_draws_outside(seed, n, draws, backlog):
    ranks = [i.to_bytes(4, "big") for i in range(n)]
    rng = random.Random(seed)
    zipf = rng.choices(ranks, weights=RS.zipf_weights(n, 0.99), k=draws)
    assert RS.hot_share_off(zipf, ranks, 0.99, backlog)[0] == 0
    off, said = RS.hot_share_off(rng.choices(ranks, k=draws), ranks, 0.99, backlog)
    assert off == 1, said
    # the hot accounts held back to the width of the backlog, at one edge: still inside
    held = [s for s in zipf if s != ranks[0]] + [ranks[0]] * max(0, zipf.count(ranks[0]) - backlog)
    assert RS.hot_share_off(held, ranks, 0.99, backlog)[0] == 0 or backlog == 0


def test_the_generators_draw_is_the_distributions(world):
    """The stream the world offered: its sources and its destinations each
    inside the band, and no payment to oneself."""
    wl = world.wl
    ranks = [wl.keys[i].public_raw for i in wl.by_rank]
    txs = [RS.parse(b) for b in wl.stream]
    assert RS.hot_share_off([t.source for t in txs], ranks, wl.constant, 0)[0] == 0
    assert RS.hot_share_off([t.ops[0][1] for t in txs], ranks, wl.constant, 0)[0] == 0
    assert all(t.source != t.ops[0][1] for t in txs)
    # who is hot is the configuration's (``hot_set_seed``), whatever the run's seed
    from benchmarks import node as N

    assert wl.ctx.config["hot_set_seed"] == 49 and wl.ctx.seed == world.seed
    assert wl.by_rank == N.permutation(49, 240, 0x5A)
    assert [k.public_raw for k in wl.keys[:3]] == [k.public_raw for k in N.keys_from_seed(49, 3)]
    # each source's payments are signed with consecutive sequence numbers, in stream order
    for src, seqs in chains(txs).items():
        assert [t.seq for t in txs if t.source == src] == seqs == list(range(seqs[0], seqs[0] + len(seqs)))


# -- the node against the plain reference ---------------------------------------------------


@pytest.mark.parametrize("row", CHECK_ROWS)
def test_check_row_is_zero(world, row):
    assert world.rows[row]["value"] == 0, world.rows[row]


def test_check_names_every_row_and_nothing_failed(world):
    assert tuple(world.rows) == CHECK_ROWS
    offered = world.wl.cursor
    assert (world.attempted, world.failed) == (offered, 0)
    assert offered == 48 + 24 * (LEDGERS - 1)
    seen = world.wl.seen
    assert seen["ledgers"] == LEDGERS and seen["txs_per_ledger"] == 24
    assert seen["longest_chain_per_ledger"] >= 3 and seen["source_accounts_per_ledger"] <= 20


def test_every_ledger_applied_each_accounts_chain_in_order(world):
    """``txhistory`` by ``txindex`` is the order a ledger was applied in."""
    for led in world.ledgers:
        applied = world.stored[led.seq]
        assert sorted(t.full_hash for t in applied) == sorted(t.full_hash for t in led.closed)
        for src, seqs in chains(applied).items():
            assert [t.seq for t in applied if t.source == src] == seqs
        # a later batch starts where an account comes round again
        batch_of = [sum(1 for u in applied[:i] if u.source == t.source) for i, t in enumerate(applied)]
        assert batch_of == sorted(batch_of)


# -- the counters and the attributes, against counts from the stream ----------------------------


def attrs_of(led, name):
    return [s.attrs for s in led.spans if s.name == name]


def test_tx_queue_counters_against_the_stream(world):
    chained = cut = closed = 0
    waited = 0.0
    for led in world.ledgers:
        chained += led.chained
        cut += len(led.at_trigger) - len(led.closed)
        # PR 51: every closed transaction was pending here first, and each
        # ledger's added their waits (the real clock: no two runs alike)
        closed += len(led.closed)
        assert led.stats["closed"] == closed
        assert waited < led.stats["pending_wait_s"] and 0.0 < led.stats["pending_wait_max_s"] <= led.stats["pending_wait_s"]
        waited = led.stats["pending_wait_s"]
        left = chains(led.left)
        assert led.stats["pending"] == len(led.left) == sum(led.stats["generations"])
        assert led.stats["accounts_pending"] == len(left)
        assert led.stats["longest_chain"] == max(map(len, chains(led.closed).values()))
        assert (led.stats["chain_txs_admitted"], led.stats["surge_cut"], led.stats["trimmed"]) == (chained, cut, 0)
        assert len(led.stats["generations"]) == 4 and led.stats["generations"][0] == 0
    assert chained > 2 * LEDGERS and cut == 24 * LEDGERS
    assert world.before["tx_queue"]["pending"] == 0 and world.after["tx_queue"] == world.ledgers[-1].stats
    assert world.info["tx_queue"] == world.ledgers[-1].stats
    assert list(world.info["tx_queue"]) == [
        "pending", "accounts_pending", "longest_chain", "generations", "chain_txs_admitted", "surge_cut", "trimmed",
        "closed", "pending_wait_s", "pending_wait_max_s",
    ]


def test_span_attributes_against_the_stream(world):
    for led in world.ledgers:
        pending, closed = chains(led.at_trigger), chains(led.closed)
        shape = lambda c: {"accounts": len(c), "longest_chain": max(map(len, c.values()))}  # noqa: E731
        (trim,) = attrs_of(led, "herder.trim_invalid")
        assert trim == {"txs": len(led.at_trigger), **shape(pending)}
        assert attrs_of(led, "herder.surge") == [{"cut": len(led.at_trigger) - 24}]
        # one walk of the set as proposed; every other question is a memo hit
        walked = [a for a in attrs_of(led, "txset.validate") if "memo" not in a]
        assert walked == [{"txs": 24, **shape(closed)}]
        assert all(set(a) == {"txs", "memo"} for a in attrs_of(led, "txset.validate") if "memo" in a)
        assert attrs_of(led, "txset.sort_for_apply") == [
            {"txs": 24, "accounts": len(closed), "batches": max(map(len, closed.values()))}
        ]
        (fees,) = attrs_of(led, "fees.charge")
        (serial,) = attrs_of(led, "apply.serial")
        assert fees["accounts"] == serial["accounts"] == len(closed) < fees["txs"] == serial["txs"] == 24
        # far fewer account rows than twice the transactions: sources and destinations repeat
        touched = set(closed) | {t.ops[0][1] for t in led.closed}
        (flush,) = attrs_of(led, "commit.flush")
        assert flush["account_rows"] == len(touched) < 48


def test_the_harness_repeats_what_compact_drops(world):
    by = {}
    for s in world.bench_spans:
        by.setdefault(s.name, []).append(s.attrs)
    want = [chains(led.closed) for led in world.ledgers]
    assert [a["longest_chain"] for a in by["bench.set_chains"]] == [max(map(len, c.values())) for c in want]
    assert [a["accounts"] for a in by["bench.apply_order"]] == [len(c) for c in want]
    assert [a["batches"] for a in by["bench.apply_order"]] == [a["longest_chain"] for a in by["bench.set_chains"]]
    assert [a["cut"] for a in by["bench.surge_cut"]] == [24] * LEDGERS


@pytest.mark.parametrize("name", READERS)
def test_layer_reader(world, name):
    import importlib
    import statistics

    read = importlib.import_module("benchmarks.layers." + name).read
    run = {
        "spans": SP.compact(s for led in world.ledgers for s in led.spans) + world.bench_spans,
        "readings": [led.reading for led in world.ledgers],
    }
    got = read(run)
    closed = [chains(led.closed) for led in world.ledgers]
    longest = statistics.median(max(map(len, c.values())) for c in closed)
    want = {
        "longest_chain_per_ledger": longest, "apply_batches_per_ledger": longest,
        "source_accounts_per_ledger": statistics.median(len(c) for c in closed), "surge_cut_txs_per_ledger": 24,
    }
    if name in want:
        assert got == want[name]
    else:
        assert 0 < got < 1000  # milliseconds a ledger of 24
    # a program without the attributes and the span (the parent): nothing to read, no error
    bare = {"spans": [s for s in run["spans"] if s.name in ("herder.trim_invalid", "herder.surge")],
            "readings": run["readings"]}
    assert (read(bare) is None) == (name != "queue_build_ms_per_ledger")
    assert read({"spans": [], "readings": run["readings"]}) is None


# -- a chain the surge filter cuts in the middle ---------------------------------------------------------


def test_a_chain_cut_in_the_middle_leaves_a_valid_prefix_and_closes_later(world):
    cuts = 0
    for i, led in enumerate(world.ledgers):
        closed, left = chains(led.closed), chains(led.left)
        for src in set(closed) & set(left):
            # what the set took of the account is the front of its chain ...
            assert closed[src][-1] < left[src][0]
            waited = [s for s in left[src] if any(t.source == src and t.seq == s for t in led.at_trigger)]
            if waited:
                cuts += 1
                # ... and the rest stayed pending and closed in a later ledger, in order
                later = [s for nxt in world.ledgers[i + 1 :] for s in chains(nxt.closed).get(src, [])]
                if i + 1 < len(world.ledgers):
                    assert later[: len(waited)] == waited[: len(later)]
    assert cuts >= 1
    # the accounts the filter made wait are the ones with the highest ids, whatever their rank
    last = world.ledgers[-1]
    assert min(chains(last.left)) > min(chains(last.closed))


# -- the counters with the tracer off; the control -----------------------------------------------------------


def test_the_counters_count_with_the_tracer_off(world, tmp_path):
    wl = SB.Workload(make_ctx(str(tmp_path), world.seed, {"TRACE_ENABLED": False}))
    try:
        for led in world.ledgers[:3]:
            wl.step(True)
            assert wl.drain_spans() == []
            stats = wl.herder.tx_queue_stats()
            # the waits are seconds of this run's own clock
            waits = {k: stats.pop(k) for k in ("pending_wait_s", "pending_wait_max_s")}
            assert stats == {k: v for k, v in led.stats.items() if k not in waits}
            assert all(v > 0.0 for v in waits.values())
    finally:
        wl.close()


def test_chain_order_control_reads_apply_order_differs(tmp_path):
    """The node (and the ``cpu`` replay, which shares its code) applies in
    XORed-hash order alone: equal ledger hashes, right balances and sequence
    numbers, and only the plain rule sees it."""
    wl = SB.Workload(make_ctx(str(tmp_path), SEEDS[0]))
    try:
        with chain_order.broken_apply_order():
            for _ in range(4):
                wl.step(True)
                wl.drain_spans()
            wl.finish()
            check = Check()
            _attempted, failed = wl.check(check)
        off = {r["name"]: r["value"] for r in check.rows if not r["ok"]}
        assert set(off) == chain_order.CATCHES and off["apply_order_differs"] >= 1 and failed == 0
    finally:
        wl.close()
