"""A validator that joined a network of residents by catch-up minimal closes
sets half of which create accounts (ISSUE 41, ``state1m``), at 2,000 residents
and width 40 on the CPU, the entry cache cut to 256 lines so that it evicts.

One world a module, built by the benchmark's own generator
(``benchmarks/generators/state_closes.py``): an archive synthesised from the
seed, a ``SIGNATURE_BACKEND="tpu"`` node (the XLA lowering of the verify kernel
on the CPU, cutover 8) caught up in mode minimal, six closes, then the cell's
whole check — a plain ``cpu`` node started on a copy of the caught-up state, the
plain reader of the archive's files and the plain ledger
(``benchmarks/reference_state.py``), which share nothing with the program.
Beside it: the batched ``Bucket.apply`` against the per-entry path, a forged
bucket file, the catch-up's deadline, the spans, counters and ``/info`` blocks
the deployment added, and the four layer readers.
"""

import copy
import gzip
import os
import shutil
import sqlite3
import struct

import numpy as np
import pytest

from benchmarks import node as N
from benchmarks import reference_state as RS
from benchmarks import spans as SP
from benchmarks.generators import state_closes as SC
from benchmarks.measure import Ctx, load_json
from benchmarks.reference import Check
from stellar_tpu.bucket import hashplane
from stellar_tpu.bucket.bucket import Bucket
from stellar_tpu.history import catchupsm
from stellar_tpu.ledger.delta import LedgerDelta
from stellar_tpu.ledger.entryframe import (
    EntryCache,
    entry_cache_of,
    ledger_key_of,
    store_add_or_change,
    store_delete_key,
)
from stellar_tpu.main.application import Application
from stellar_tpu.tx import testutils as T
from stellar_tpu.util.clock import VirtualClock
from stellar_tpu.xdr.entries import (
    AccountEntry,
    Asset,
    LedgerEntry,
    LedgerEntryData,
    LedgerEntryType,
    OfferEntry,
    Price,
    PublicKey,
    Signer,
    TrustLineEntry,
)
from stellar_tpu.xdr.ledger import BucketEntryType, LedgerHeader

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESIDENTS, WIDTH, CLOSES, SEED, LINES = 2000, 40, 6, 41, 256
# the rows of the cell's check, in its order (``Workload.check``)
CHECK_ROWS = (
    "invariant_violations", "closes_not_invariant_checked", "durable_lcl_seq_behind", "durable_lcl_hash_differs",
    "closed_txs_not_yet_in_txhistory", "txs_not_in_txhistory", "ledger_hashes_differing",
    "anchor_bucket_list_hash_differs", "archive_buckets_off",
) + RS.ROWS


def make_ctx(work: str, seed: int = SEED) -> Ctx:
    config = copy.deepcopy(load_json(os.path.join(ROOT, "benchmarks", "configs", "state1m.json")))
    config["rehearsal"] = {
        "width": WIDTH, "accounts": RESIDENTS,
        "node": {"DESIRED_MAX_TX_PER_LEDGER": WIDTH, "TPU_CPU_CUTOVER": 8, "SIG_BATCH_MAX": 16},
    }
    traffic = copy.deepcopy(load_json(os.path.join(ROOT, "benchmarks", "traffic", "state-ledgers.json")))
    traffic["params"]["rehearsal_sets"] = CLOSES
    return Ctx(seed=seed, config=config, traffic=traffic, cell=None, work=work, rehearsal=True, root=ROOT, seconds=1.0)


class World:
    pass


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    patch = pytest.MonkeyPatch()
    patch.setattr(EntryCache, "CAPACITY", LINES)
    w = World()
    w.work = str(tmp_path_factory.mktemp("state-close"))
    wl = w.wl = SC.Workload(make_ctx(w.work))
    try:
        w.catchup_spans = wl.drain_spans()
        w.before = wl.counters()
        w.readings, w.close_spans = [], []
        for _ in range(CLOSES):
            w.readings.append(wl.step(True))
            w.close_spans.append(wl.drain_spans())
        w.after = wl.counters()
        w.info = wl.node.app.command_handler.handle_info({})["info"]
        w.bucket_dir = wl.node.cfg.BUCKET_DIR_PATH
        # the copy taken after the catch-up, before the plain node closes on it
        con = sqlite3.connect(os.path.join(wl.plain_dir, "node.db"))
        w.rows_at_anchor = con.execute("SELECT COUNT(*), SUM(balance) FROM accounts").fetchone()
        con.close()
        wl.finish()
        check = Check()
        w.attempted, w.failed = wl.check(check)
        w.rows = {r["name"]: r for r in check.rows}
        yield w
    finally:
        wl.close()
        patch.undo()


# -- the cell's own check: both nodes, the plain reader, the plain ledger -------------


@pytest.mark.parametrize("row", CHECK_ROWS)
def test_check_row_is_zero(world, row):
    """Ledger hashes equal a cpu node's on the copied state; every touched,
    created and sampled row, the row count, the balance sum, the fee pool,
    every result code and verdict equal the plain ledger's."""
    assert world.rows[row]["value"] == 0, world.rows[row]


def test_check_names_every_row_and_nothing_failed(world):
    assert tuple(world.rows) == CHECK_ROWS
    assert (world.attempted, world.failed) == (CLOSES * WIDTH, 0)
    assert world.rows["ledger_hashes_differing"]["detail"].startswith(f"of {CLOSES} closes")


def test_plain_reader_hashes_equal_the_programs(world):
    """Each bucket file: the plain reader's hash of the archive's gzip is the
    program's of the file the node adopted; the levels and the list hash to
    what the node held at the anchor and the header states."""
    state = RS.read_archive(world.wl.archive_dir, world.wl.anchor)
    adopted = sorted(f for f in os.listdir(world.bucket_dir) if f.startswith("bucket-"))
    seen = 0
    for name in adopted:
        h = bytes.fromhex(name[len("bucket-"):-len(".xdr")])
        gz = RS.bucket_path(world.wl.archive_dir, h)
        if not os.path.exists(gz):
            continue  # a bucket the closes made
        seen += 1
        assert RS.read_bucket(gz)[0] == h == hashplane.hash_file(os.path.join(world.bucket_dir, name))[0]
    assert seen == state["buckets"] == len(world.wl.archive["levels"])
    assert state["bucket_list_hash"] == world.wl.at_anchor["bucket_list_hash"] == state["header"]["bucket_list_hash"]
    assert state["header"]["hash"] == world.wl.at_anchor["lcl"] and state["header"]["max_tx_set_size"] == WIDTH
    assert len(state["accounts"]) == RESIDENTS and state["buckets_off"] == 0


def test_every_resident_is_a_row_at_level_five_or_deeper(world):
    levels = world.wl.archive["levels"]
    assert sum(levels.values()) == RESIDENTS and min(levels) >= 5
    ages = world.wl.anchor + 1 - world.wl.archive["modified"]
    assert ages.min() > SC.level_bounds()[4]
    assert world.rows_at_anchor == (RESIDENTS, RESIDENTS * world.wl.p["resident_balance"])


def test_level_rule():
    bounds = SC.level_bounds()
    assert bounds[:5] == [4, 20, 84, 340, 1364] and len(bounds) == SC.NUM_LEVELS
    ages = np.array([1, 4, 5, 1364, 1365, bounds[9], bounds[9] + 1, 10**9])
    assert SC.level_of_age(ages).tolist() == [0, 0, 1, 4, 5, 9, 10, 10]


def test_public_keys_are_the_harness_keys():
    keys = N.keys_from_seed(SEED, 5)
    raw = SC.derive_public_keys(b"acct", SEED, 0, 5)
    assert [raw[i * 32 : i * 32 + 32] for i in range(5)] == [k.public_raw for k in keys]
    assert SC.derive_public_keys(b"acct", SEED, 3, 5) == raw[96:]


# -- spans, counters, /info ----------------------------------------------------------------


def test_bucket_apply_spans_and_history_counters(world):
    spans = [s for s in world.catchup_spans if s.name == "bucket.apply"]
    levels = world.wl.archive["levels"]
    # oldest level first, one span a bucket, each under the round
    assert [(s.attrs["level"], s.attrs["entries"]) for s in spans] == sorted(levels.items(), reverse=True)
    (rnd,) = [s for s in world.catchup_spans if s.name == "catchup.round"]
    assert all(s.parent == rnd.sid for s in spans) and rnd.attrs["mode"] == "minimal"
    h = world.info["history"]
    assert h["bucket_apply_entries"] == RESIDENTS
    assert sum(s.end - s.start for s in spans) <= h["bucket_apply_s"] < rnd.end - rnd.start
    assert world.before["history"]["bucket_apply_s"] == h["bucket_apply_s"]


def test_accounts_warm_span_a_close(world):
    """A reading validates the set, then closes it: the set's accounts are
    loaded where its triples are collected, and the close's own ask finds
    them — but for a line that was there before the collect's warm and old
    enough for the warm's own puts to push it out of the 256."""
    half = WIDTH // 2
    for spans in world.close_spans:
        at_collect, at_close = [s for s in spans if s.name == "accounts.warm"]
        (validate,) = [s for s in spans if s.name == "txset.validate"]
        (collect,) = [s for s in spans if s.name == "sig.collect"]
        (close,) = [s for s in spans if s.name == "ledger.close"]
        assert (at_collect.attrs["site"], at_close.attrs["site"]) == ("collect", "close")
        assert at_collect.parent == validate.sid == collect.parent and at_collect.end <= collect.start
        assert at_close.parent == close.sid
        a = at_collect.attrs
        # 3 x half residents and half destinations that do not exist yet
        assert a["asked"] == 4 * half
        assert a["rows"] + half <= a["missed"] <= a["asked"]
        assert a["selects"] == -(-a["missed"] // 500)
        c = at_close.attrs
        assert c["asked"] == 4 * half and c["rows"] <= c["missed"] <= a["asked"] - a["missed"]
        assert c["selects"] == -(-c["missed"] // 500)
        # the hint-matching loop ran on lines: one account a transaction, all there
        assert collect.attrs["accounts"] == WIDTH


def test_entry_cache_block_counts(world):
    b, a = world.before["entry_cache"], world.info["entry_cache"]
    assert set(a) == {"hits", "misses", "evictions", "warm_asked", "sql_loads", "lines", "capacity"}
    assert a["capacity"] == LINES and a["lines"] == LINES
    # once a set, though a reading asks twice (the collect's warm, the close's)
    assert a["warm_asked"] - b["warm_asked"] == CLOSES * 2 * WIDTH
    # 2,000 residents against 256 lines: most of a set's residents are asked of SQL
    asked_of_sql = a["sql_loads"] - b["sql_loads"]
    assert CLOSES * WIDTH < asked_of_sql <= CLOSES * (2 * WIDTH + 16 + WIDTH)
    # the catch-up left the cache full; every line a close adds pushes one out
    assert b["evictions"] == RESIDENTS - LINES and a["evictions"] - b["evictions"] >= asked_of_sql - CLOSES * 16
    assert a["hits"] > b["hits"] and a["misses"] >= b["misses"]


def test_layer_readers(world):
    from benchmarks.layers import (
        account_rows_loaded_per_close, accounts_warm_ms_per_close, bucket_apply_s_setup, entry_cache_hit_pct,
    )

    spans = SP.compact([s for close in world.close_spans for s in close])
    run = {"counters": {"before": world.before, "after": world.after}, "spans": spans, "readings": world.readings}
    loaded = account_rows_loaded_per_close.read(run)
    assert WIDTH < loaded <= 3 * WIDTH + 16
    assert entry_cache_hit_pct.read(run) == pytest.approx(100.0 * (1 - loaded / (2 * WIDTH)) if loaded < 2 * WIDTH else 0.0)
    assert 0 < accounts_warm_ms_per_close.read(run) < 1000
    assert bucket_apply_s_setup.read(run) == world.info["history"]["bucket_apply_s"]
    # a program without the spans and the blocks (the parent): nothing, and no raise
    bare = {k: {"sig_backend": {}, "applied_tx": v["applied_tx"], "history": {"rounds": 1}} for k, v in run["counters"].items()}
    old = {"counters": bare, "spans": [s for s in spans if s.name != "accounts.warm"], "readings": world.readings}
    for reader in (account_rows_loaded_per_close, accounts_warm_ms_per_close, bucket_apply_s_setup, entry_cache_hit_pct):
        assert reader.read(old) is None


# -- Bucket.apply: a batch of rows a statement against an entry at a time ------------------


def key_of(n: int) -> PublicKey:
    return PublicKey.from_ed25519(n.to_bytes(4, "big") + b"\xcd" * 28)


def account(n: int, balance: int, signers=()) -> LedgerEntry:
    ae = AccountEntry(
        accountID=key_of(n), balance=balance, seqNum=n << 32, numSubEntries=len(signers), inflationDest=None, flags=0,
        homeDomain="", thresholds=b"\x01\x00\x00\x00", signers=[Signer(key_of(s), w) for s, w in signers], ext=0,
    )
    return LedgerEntry(7 + n, LedgerEntryData(LedgerEntryType.ACCOUNT, ae), 0)


def trustline(n: int, issuer: int, balance: int) -> LedgerEntry:
    line = TrustLineEntry(key_of(n), Asset.alphanum4(b"USD\x00", key_of(issuer)), balance, 10**12, 1, 0)
    return LedgerEntry(9, LedgerEntryData(LedgerEntryType.TRUSTLINE, line), 0)


def offer(n: int, offer_id: int, amount: int) -> LedgerEntry:
    o = OfferEntry(key_of(n), offer_id, Asset.native(), Asset.alphanum4(b"USD\x00", key_of(1)), amount, Price(3, 2), 0, 0)
    return LedgerEntry(11, LedgerEntryData(LedgerEntryType.OFFER, o), 0)


def older_bucket():
    # signers out of raw-key order: both paths store them in it
    live = [account(i, 1000 + i) for i in range(1, 40)]
    live += [account(50, 5, signers=((90, 2), (60, 1), (75, 3))), account(51, 6, signers=((61, 1),))]
    live += [trustline(2, 1, 77), trustline(3, 1, 78), offer(4, 1, 500), offer(5, 2, 600)]
    return live, []


def younger_bucket():
    live = [account(3, 9999), account(50, 7, signers=((60, 4),)), account(41, 1), trustline(2, 1, 80), offer(4, 1, 450)]
    dead = [ledger_key_of(e) for e in (account(5, 0), account(51, 0), trustline(3, 1, 0), offer(5, 2, 0), account(77, 0))]
    return live, dead


def per_entry_apply(bucket, db) -> None:
    """``Bucket.apply`` as it was: a throwaway delta an entry."""
    with db.transaction():
        for e in bucket:
            delta = LedgerDelta(LedgerHeader(), db, update_last_modified=False)
            if e.type == BucketEntryType.LIVEENTRY:
                store_add_or_change(e.value, delta, db)
            else:
                store_delete_key(e.value, delta, db)
            delta.commit()


def dump(db) -> dict:
    return {
        t: sorted(db.query_all(f"SELECT * FROM {t}"), key=repr)
        for t in ("accounts", "signers", "trustlines", "offers")
    }


@pytest.fixture
def two_apps():
    apps = []
    for instance in (141, 142):
        clock = VirtualClock()
        apps.append((Application(clock, T.get_test_config(instance), new_db=True), clock))
    yield [a for a, _ in apps]
    for a, clock in apps:
        a.database.close()
        clock.shutdown()


@pytest.mark.parametrize("layers", ["one bucket", "a younger bucket with dead entries over it"])
def test_batched_bucket_apply_leaves_the_per_entry_rows(two_apps, monkeypatch, layers):
    batched, entrywise = two_apps
    monkeypatch.setattr(Bucket, "APPLY_BATCH", 16)  # several batches a bucket, a last partial one
    buckets = [older_bucket()] + ([younger_bucket()] if layers != "one bucket" else [])
    for live, dead in buckets:
        b = Bucket.fresh(batched.bucket_manager, live, dead)
        assert b.apply(batched.database) == len(live) + len(dead)
        per_entry_apply(Bucket.fresh(entrywise.bucket_manager, live, dead), entrywise.database)
    rows = dump(batched.database)
    assert rows == dump(entrywise.database)
    assert len(rows["signers"]) == (4 if layers == "one bucket" else 1)
    # and the cache lines: what a load answers, row or known-absent
    from stellar_tpu.ledger.accountframe import AccountFrame

    for n in (3, 5, 50, 51, 77):
        got = [AccountFrame.load_account(key_of(n), a.database) for a in (batched, entrywise)]
        assert [None if f is None else f.entry.to_xdr() for f in got][0] == (None if got[1] is None else got[1].entry.to_xdr())
    assert Bucket().apply(batched.database) == 0
    assert entry_cache_of(batched.database).stats()["evictions"] == 0


# -- a lying archive, a catch-up that never ends ---------------------------------------------


def fresh_node(work: str, archive_dir: str):
    from benchmarks.generators.replay import archive_of

    os.makedirs(work)
    ctx = make_ctx(work)
    cfg = N.make_config(ctx.config, work, True, ctx.traffic.get("node"))
    cfg.SIGNATURE_BACKEND = "cpu"
    cfg.HISTORY = archive_of(cfg.HISTORY, archive_dir, False)
    return N.Node(cfg, WIDTH)


def test_forged_bucket_file_is_refused(world, tmp_path, monkeypatch):
    """One resident's balance raised inside a bucket file, under the file's
    old name: the catch-up fails, and no resident becomes a row."""
    monkeypatch.setattr(catchupsm, "MAX_RETRIES", 1)
    monkeypatch.setattr(catchupsm, "RETRY_DELAY_SECONDS", 0.01)
    archive_dir = str(tmp_path / "archive")
    shutil.copytree(world.wl.archive_dir, archive_dir)
    level = max(world.wl.archive["levels"])
    has = load_json(os.path.join(archive_dir, ".well-known", "stellar-history.json"))
    path = RS.bucket_path(archive_dir, bytes.fromhex(has["currentBuckets"][level]["curr"]))
    with gzip.open(path, "rb") as f:
        data = bytearray(f.read())
    at = 4 + SC.AT_BALANCE
    data[at : at + 8] = struct.pack(">q", 10**15)
    with gzip.open(path, "wb") as f:
        f.write(bytes(data))
    node = fresh_node(str(tmp_path / "node"), archive_dir)
    try:
        with pytest.raises(RuntimeError, match="the catch-up failed"):
            SC.Workload.catch_up(node, 60.0)
        assert node.lm.last_closed.header.ledgerSeq == 1
        assert node.app.database.query_one("SELECT COUNT(*) FROM accounts")[0] == 1  # the genesis account
    finally:
        node.stop()


def test_catch_up_deadline_raises(world, tmp_path):
    node = fresh_node(str(tmp_path / "node"), world.wl.archive_dir)
    try:
        with pytest.raises(RuntimeError, match="passed its deadline of 0 s"):
            SC.Workload.catch_up(node, 0.0)
    finally:
        node.stop()
