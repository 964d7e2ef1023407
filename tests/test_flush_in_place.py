"""A close's entry writes stay in place and in memory until the COMMIT
(ISSUE 42): the store buffer's flush is ``INSERT … ON CONFLICT (pk) DO UPDATE``
on both databases, and sqlite's page cache holds one close's dirty pages.

- ``test_closes_update_rows_where_they_lie``: one case an operation — pay,
  create, change signers, change trust, make / cross / cancel offers.  Every
  close runs on a buffered node (the flush under test), on a write-through
  node (``ENTRY_WRITE_BUFFER`` off: an INSERT or an UPDATE a store, no flush)
  and on the plain ledger (``tests/reference_apply.py``): equal ledger
  hashes, the four entry tables equal read in key order and equal to the
  plain ledger's, and on the buffered node's file, read by ``sqlite3``
  alone, a row the close only changed keeps its rowid and a new one takes
  the next (``rowids_taken`` — the span's, the counter's — equals the
  accounts created).
- ``test_a_merge_…``: the plain ledger has no ACCOUNT_MERGE, so the deleted
  row is held to the write-through node and to what a merge must leave.
- ``test_bucket_apply_…``: ``Bucket.apply`` over live-then-dead-then-live
  entries leaves the rows the per-entry path leaves.
- the page-cache half on a file of ~60,000 accounts: a flush of 5,000 random
  rows puts no frame in a fresh ``-wal`` before the COMMIT, and under the
  2 MB cache the node ran with before it does; the settings this PR must not
  move read back as they were; ``/info`` ``database``.
"""

import os
import random
import sqlite3

import pytest
from test_mixed_close import BIG, HELD, RESERVE, Node, World, asks, market
from test_state_close import account, offer, per_entry_apply, trustline
from test_storebuffer import _dump_entry_tables, _ScenarioRunner, _seq

from stellar_tpu.bucket.bucket import Bucket
from stellar_tpu.database import database as dbmod
from stellar_tpu.database.database import Database
from stellar_tpu.database.dialect import PostgresDialect, upsert_sql
from stellar_tpu.ledger.accountframe import AccountFrame
from stellar_tpu.ledger.entryframe import ledger_key_of
from stellar_tpu.ledger.offerframe import OfferFrame
from stellar_tpu.ledger.trustframe import TrustFrame
from stellar_tpu.main.application import Application
from stellar_tpu.tx import testutils as T
from stellar_tpu.util import VIRTUAL_TIME, VirtualClock

KEYS = {
    "accounts": "accountid",
    "signers": "accountid, publickey",
    "trustlines": "accountid, issuer, assetcode",
    "offers": "offerid",
}
IN_PLACE = ("accounts", "trustlines", "offers")  # the flush rewrites an account's signer rows wholesale


def rowids(db_path: str, table: str) -> dict:
    """{key: rowid} of ``table``, by ``sqlite3`` alone."""
    con = sqlite3.connect(f"file:{db_path}?mode=ro", uri=True)
    try:
        return {tuple(r[1:]): r[0] for r in con.execute(f"SELECT rowid, {KEYS[table]} FROM {table}")}
    finally:
        con.close()


def kept_and_next(before: dict, after: dict) -> int:
    """Hold ``after`` to an update in place: a key on both sides has the
    rowid it had, and the new keys took the rowids after the highest one
    that stayed.  -> new keys."""
    stayed = before.keys() & after.keys()
    assert all(before[k] == after[k] for k in stayed), "a row the close only changed took another rowid"
    top = max((after[k] for k in stayed), default=0)
    fresh = sorted(after[k] for k in after.keys() - before.keys())
    assert fresh == list(range(top + 1, top + 1 + len(fresh)))
    return len(fresh)


class InPlaceWorld(World):
    def __init__(self, tmp, instance: int):
        super().__init__(tmp, instance)
        # the second node writes through: the close reads the switch, so every store of it
        # is an INSERT or an UPDATE of its own and no flush statement ever runs
        through = Node(instance + 1, "cpu", str(tmp / "written-through.db"))
        through.app.config.ENTRY_WRITE_BUFFER = False
        self.nodes.append(through)
        self.taken = []  # rowids_taken, a close

    def close(self, plan, **kw):
        node = self.nodes[0]
        db = node.app.database
        before = {t: rowids(node.db_path, t) for t in IN_PLACE}
        counted = db.stats()["rowids_taken"]
        node.app.tracer.clear()
        done = super().close(plan, **kw)  # hashes equal, every table the plain ledger's
        if done is None:
            return None
        new = {t: kept_and_next(before[t], rowids(node.db_path, t)) for t in IN_PLACE}
        (flush,) = [s for s in node.app.tracer.spans() if s.name == "commit.flush"]
        assert flush.attrs["rowids_taken"] == db.stats()["rowids_taken"] - counted == new["accounts"]
        assert _dump_entry_tables(db) == _dump_entry_tables(self.nodes[1].app.database)
        self.taken.append(new["accounts"])
        return done


@pytest.fixture
def world(tmp_path):
    w = InPlaceWorld(tmp_path, 230)
    yield w
    w.stop()


def case_pay(w):
    w.fund("A", "B", "C")
    assert w.taken == [3]
    w.close([("A", [("pay", w.n("B"), 5, None)]), ("B", [("pay", w.n("C"), 6, None)]),
             ("C", [("pay", w.n("A"), 10**15, None)])])  # the third is underfunded and unwinds
    w.close([("A", [("pay", w.n("C"), 7, None)])])
    assert w.taken[1:] == [0, 0]


def case_create(w):
    w.fund("A", "B")
    # four creates, one of an account that exists and one under the reserve, beside a payment
    w.close([("A", [("create", w.n(x), 9 * RESERVE) for x in ("n1", "n2")]), ("B", [("create", w.n("n3"), 3 * RESERVE)]),
             ("B", [("create", w.n("A"), 3 * RESERVE)]), ("A", [("create", w.n("poor"), RESERVE)]),
             ("B", [("pay", w.n("A"), 9, None)])])
    # a created account pays and creates in the next close
    w.close([("n1", [("pay", w.n("n2"), 5, None)]), ("n2", [("create", w.n("n4"), RESERVE * 2)])])
    assert w.taken == [2, 3, 1]


def case_signers(w):
    w.fund("A", "B")
    a, s, t = w.n("A"), w.n("S"), w.n("T")
    w.close([("A", [("options", (("signer", (s, 1)),)), ("options", (("signer", (t, 2)),))]),
             ("B", [("pay", a, 5, None)])])
    w.close([("A", [("options", (("signer", (s, 3)),))])])  # reweighed
    w.close([("A", [("options", (("signer", (t, 0)),))], (s,)), ("B", [("options", (("signer", (s, 1)),))])])
    assert w.plain.signers[a] == {s: 3} and w.taken[1:] == [0, 0, 0]


def case_trust(w):
    usd = market(w)  # four lines made, each paid into
    eur = w.asset("EUR", "J")
    w.close([("A", [("trust", eur, BIG)]), ("B", [("trust", usd, 2 * HELD)]), ("E", [("trust", usd, BIG)])])
    w.close([("A", [("trust", eur, 0)]), ("E", [("trust", usd, 0)]), ("C", [("pay", w.n("D"), 50, usd)])])
    assert (w.n("A"), eur) not in w.plain.trustlines and w.plain.trustlines[(w.n("B"), usd)][1] == 2 * HELD
    assert set(w.taken[1:]) == {0}


def case_offers_made(w):
    usd = market(w)
    asks(w, usd, ("B", 1000, 101), ("C", 1000, 102))
    # two more in one close, and one re-priced by id
    w.close([("B", [("offer", usd, None, 500, (103, 100), 0)]), ("D", [("offer", usd, None, 400, (104, 100), 0)]),
             ("C", [("offer", usd, None, 900, (105, 100), 2)])])
    assert w.plain.offers[2][3:] == (900, 105, 100) and len(w.plain.offers) == 4


def case_offers_crossed(w):
    usd = market(w)
    asks(w, usd, ("B", 1000, 101), ("C", 1000, 102), ("B", 1000, 103))
    # the first ask taken whole (its row goes), the second in part (its row stays where it lies)
    w.close([("A", [("path", w.n("D"), None, 9000, usd, 1400, ())])])
    assert {i: o[3] for i, o in w.plain.offers.items()} == {2: 600, 3: 1000}
    # an arriving bid crosses what is left of the second and rests
    w.close([("D", [("offer", None, usd, 2000, (98, 100), 0)])])
    assert sorted(w.plain.offers) == [3, 4]


def case_offers_cancelled(w):
    usd = market(w)
    asks(w, usd, ("B", 1000, 101), ("C", 1000, 102), ("B", 1000, 103))
    w.close([("B", [("offer", usd, None, 0, (101, 100), 1)]), ("C", [("offer", usd, None, 0, (102, 100), 2)])])
    # with the highest rowid gone the next offer takes the one after what stayed
    w.close([("B", [("offer", usd, None, 0, (103, 100), 3)]), ("C", [("offer", usd, None, 10, (110, 100), 0)])])
    assert list(w.plain.offers) == [4]


CASES = {name[5:]: fn for name, fn in sorted(globals().items()) if name.startswith("case_")}


@pytest.mark.parametrize("case", sorted(CASES))
def test_closes_update_rows_where_they_lie(world, case):
    CASES[case](world)
    assert world.nodes[0].app.invariants.total_violations == 0


def test_a_merge_deletes_its_row_and_a_create_beside_it_takes_the_next(tmp_path):
    clock = VirtualClock(VIRTUAL_TIME)
    r = _ScenarioRunner(clock, 234, tmp=tmp_path)  # node 0 buffered, node 1 written through; hashes and rows held equal
    try:
        a, b, c, d = (T.get_account(f"in-place-{x}") for x in "abcd")
        path = r.db_paths[0]
        r.close(lambda app, root: [
            T.tx_from_ops(app, root, _seq(app, root), [T.create_account_op(k, 10**10) for k in (a, b, c)]),
        ])
        before = rowids(path, "accounts")
        db = r.apps[0].database
        counted = db.stats()["rowids_taken"]
        # c, the newest row, merges into a; b pays a and creates d
        codes = r.close(lambda app, root: [
            T.tx_from_ops(app, c, _seq(app, c), [T.merge_op(a)]),
            T.tx_from_ops(app, b, _seq(app, b), [T.payment_op(a, 10**6), T.create_account_op(d, 10**9)]),
        ])
        assert [code.name for code in codes] == ["txSUCCESS", "txSUCCESS"]
        after = rowids(path, "accounts")
        assert kept_and_next(before, after) == 1 == db.stats()["rowids_taken"] - counted
        gone = (AccountFrame.load_account(c.get_public_key(), db), len(before) - len(after))
        assert gone == (None, 0)  # one row went, one came
        # the merged account made again is a new row
        r.close(lambda app, root: [
            T.tx_from_ops(app, root, _seq(app, root), [T.create_account_op(c, 10**10)]),
        ])
        assert kept_and_next(after, rowids(path, "accounts")) == 1
    finally:
        r.shutdown()
        clock.shutdown()


# -- Bucket.apply takes the same statement ------------------------------------------------------------


def test_bucket_apply_over_live_then_dead_then_live_entries(monkeypatch):
    apps = []
    for instance in (236, 237):
        clock = VirtualClock()
        apps.append((Application(clock, T.get_test_config(instance), new_db=True), clock))
    (batched, _), (entrywise, _) = apps
    monkeypatch.setattr(Bucket, "APPLY_BATCH", 8)
    oldest = [account(i, 100 + i) for i in range(1, 30)] + [account(40, 5, signers=((90, 2), (60, 1)))]
    oldest += [trustline(2, 1, 77), trustline(3, 1, 78), offer(4, 1, 500), offer(5, 2, 600)]
    deaths = [ledger_key_of(e) for e in (account(5, 0), account(29, 0), account(40, 0), trustline(3, 1, 0), offer(5, 2, 0))]
    again = [account(5, 1), account(29, 2), account(40, 9, signers=((61, 1),)), trustline(3, 1, 1), offer(5, 2, 7)]
    layers = [(oldest, []), ([account(7, 7777), trustline(2, 1, 80)], deaths), (again + [account(8, 8888)], [])]
    try:
        marks = []
        for live, dead in layers:
            b = Bucket.fresh(batched.bucket_manager, live, dead)
            assert b.apply(batched.database) == len(live) + len(dead)
            per_entry_apply(Bucket.fresh(entrywise.bucket_manager, live, dead), entrywise.database)
            assert _dump_entry_tables(batched.database) == _dump_entry_tables(entrywise.database)
            marks.append(dict(batched.database.query_all("SELECT accountid, rowid FROM accounts")))
        first, second, third = marks
        assert len(first) == 31 and len(second) == 28 and len(third) == 31  # the root account beside them
        # what a younger bucket only changes stays where it lay; what died and lives again is a new row
        assert all(second[k] == first[k] for k in second) and all(third[k] == second[k] for k in second)
        assert min(third[k] for k in third.keys() - second.keys()) > max(second.values())
        assert batched.database.stats()["rowids_taken"] == 30 + 0 + 3
        assert len(batched.database.query_all("SELECT * FROM signers")) == 1
    finally:
        for app, clock in apps:
            app.database.close()
            clock.shutdown()


# -- one spelling, both databases ------------------------------------------------------------------------


@pytest.mark.parametrize("frame, table", [(AccountFrame, "accounts"), (TrustFrame, "trustlines"), (OfferFrame, "offers")])
def test_the_flush_statement_is_spelled_once_for_both_databases(frame, table):
    sql = frame._UPSERT_SQL
    pk = PostgresDialect.upsert_conflict_targets[table]
    assert sql.startswith(f"INSERT INTO {table} (") and f" ON CONFLICT ({', '.join(pk)}) DO UPDATE SET " in sql
    assert "REPLACE" not in sql.upper()
    cols = [c.strip() for c in sql[sql.index("(") + 1:sql.index(")")].split(",")]
    assert sql == upsert_sql(table, ", ".join(cols))
    # every column but the key is set, the key never
    sets = sql.split(" DO UPDATE SET ")[1].split(", ")
    assert sets == [f"{c}=EXCLUDED.{c}" for c in cols if c not in pk]
    # postgres is handed the same text: its rewrite is the identity here, and what it made of the
    # INSERT OR REPLACE this statement was until PR 42 is this statement
    d = PostgresDialect()
    assert d.rewrite(sql) == sql
    was = f"INSERT OR REPLACE INTO {table} ({', '.join(cols)}) VALUES ({','.join('?' * len(cols))})"
    assert d.rewrite(was) == sql
    assert d.translate(sql) == sql.replace("?", "%s")


# -- the page cache ----------------------------------------------------------------------------------------

RESIDENTS = 60_000


@pytest.fixture(scope="module")
def big(tmp_path_factory):
    """A file database of 60,000 accounts (~11 MB), opened as a node opens it."""
    path = str(tmp_path_factory.mktemp("big") / "state.db")
    db = Database(f"sqlite3://{path}")
    AccountFrame.drop_all(db)
    with db.transaction():
        AccountFrame.upsert_batch(db, [account(n, 10**9 + n) for n in range(RESIDENTS)], [False] * RESIDENTS)
    assert db.stats()["rowids_taken"] == RESIDENTS
    yield db, path
    db.close()


def wal_bytes_before_the_commit(db, path: str, rng, balance: int) -> int:
    """Flush 5,000 random residents inside a transaction, as a close does,
    over a ``-wal`` just truncated -> the bytes it holds before the COMMIT."""
    drawn = [account(n, balance + n) for n in rng.sample(range(RESIDENTS), 5000)]
    db._conn.execute("PRAGMA wal_checkpoint(TRUNCATE)")
    assert os.path.getsize(path + "-wal") == 0
    taken = db.stats()["rowids_taken"]
    with db.transaction():
        AccountFrame.upsert_batch(db, drawn, [False] * len(drawn))
        held = os.path.getsize(path + "-wal")
    assert os.path.getsize(path + "-wal") > 0  # the COMMIT wrote them
    assert db.stats()["rowids_taken"] == taken  # all 5,000 updated where they lay
    n = drawn[0].data.value
    assert db.query_one("SELECT balance FROM accounts WHERE accountid=?", (AccountFrame._sql_row(n, 0)[-1],)) == (n.balance,)
    return held


def test_a_close_s_dirty_pages_wait_in_the_cache_for_the_commit(big):
    db, path = big
    assert os.path.getsize(path) > 10 * 2**20
    rng = random.Random(42)
    assert wal_bytes_before_the_commit(db, path, rng, 2 * 10**9) == 0
    # the test can see a spill: under sqlite's default, which the node ran with until PR 42, the same flush leaves frames
    db._conn.execute("PRAGMA cache_size=-2000")
    try:
        assert wal_bytes_before_the_commit(db, path, rng, 3 * 10**9) > 100 * 4096
    finally:
        db._conn.execute(f"PRAGMA cache_size=-{dbmod.SQLITE_CACHE_KIB}")
    assert wal_bytes_before_the_commit(db, path, rng, 4 * 10**9) == 0


def test_the_settings_read_back_as_the_parent_s(big):
    db, _path = big
    s = db.stats()
    assert (s["journal_mode"], s["synchronous"], s["wal_autocheckpoint"]) == ("wal", 0, 1000)
    assert s["cache_kib"] == dbmod.SQLITE_CACHE_KIB and s["page_size"] == 4096
    assert s["page_count"] * s["page_size"] == os.path.getsize(_path)
    mem = Database("sqlite3://:memory:")
    try:
        m = mem.stats()
        assert (m["journal_mode"], m["synchronous"], m["cache_kib"]) == ("memory", 0, dbmod.SQLITE_CACHE_KIB)
    finally:
        mem.close()


def test_info_has_a_database_block(tmp_path):
    node = Node(238, "cpu", str(tmp_path / "info.db"))
    try:
        block = node.app.command_handler.handle_info({})["info"]["database"]
        assert sorted(block) == sorted(
            ["journal_mode", "synchronous", "wal_autocheckpoint", "cache_kib", "page_size", "page_count", "rowids_taken"]
        )
        assert block["journal_mode"] == "wal" and block["rowids_taken"] == 0  # genesis is written through, not flushed
        # no Config field and no environment variable sizes the cache
        assert not [k for k in vars(node.app.config) if "CACHE_KIB" in k or "PAGE_CACHE" in k]
    finally:
        node.stop()
