"""The key of `txhistory` / `txfeehistory` (schema 2, PR 35): both tables are
keyed by (ledgerseq, txindex), the order a close writes its rows in and every
reader asks for them by, and carry no other index; a database of schema 1
(keyed (txid, ledgerseq), a second index by ledgerseq) is rebuilt once, in one
transaction, when the node opens it."""

import glob
import gzip
import os
import shutil
import sqlite3
import subprocess
import sys
import textwrap

import pytest
from test_serial_apply import close, funded, pay

from stellar_tpu.database.database import SCHEMA_VERSION, Database
from stellar_tpu.tx import history as tx_history
from stellar_tpu.tx import testutils as T
from stellar_tpu.util import fs

TABLES = ("txhistory", "txfeehistory")

# schema 1, as every release before PR 35 created it
OLD_DDL = (
    "DROP TABLE IF EXISTS txhistory",
    "DROP TABLE IF EXISTS txfeehistory",
    """CREATE TABLE txhistory (
        txid      CHARACTER(64) NOT NULL,
        ledgerseq INT NOT NULL CHECK (ledgerseq >= 0),
        txindex   INT NOT NULL,
        txbody    TEXT NOT NULL,
        txresult  TEXT NOT NULL,
        txmeta    TEXT NOT NULL,
        PRIMARY KEY (txid, ledgerseq)
    )""",
    "CREATE INDEX histbyseq ON txhistory (ledgerseq)",
    """CREATE TABLE txfeehistory (
        txid      CHARACTER(64) NOT NULL,
        ledgerseq INT NOT NULL CHECK (ledgerseq >= 0),
        txindex   INT NOT NULL,
        txchanges TEXT NOT NULL,
        PRIMARY KEY (txid, ledgerseq)
    )""",
    "CREATE INDEX histfeebyseq ON txfeehistory (ledgerseq)",
)


def to_schema_1(db):
    """Give an initialized, still empty database the old tables and version."""
    for sql in OLD_DDL:
        db.execute(sql)
    db.put_schema_version(1)


def key_of(db, table):
    """(the primary key's columns in key order, every index's columns)."""
    info = db.query_all(f"PRAGMA table_info({table})")
    key = [name for _cid, name, _t, _nn, _d, pk in sorted(info, key=lambda r: r[5]) if pk]
    indexes = [
        [col[2] for col in db.query_all(f"PRAGMA index_info({idx[1]})")]
        for idx in db.query_all(f"PRAGMA index_list({table})")
    ]
    return key, indexes


def rows_by_rowid(db, table):
    return db.query_all(f"SELECT * FROM {table} ORDER BY rowid")


def rows_by_key(db, table):
    return db.query_all(f"SELECT * FROM {table} ORDER BY ledgerseq, txindex")


def file_node(tmp_path, instance, new_db):
    from stellar_tpu.main.application import Application
    from stellar_tpu.util.clock import VIRTUAL_TIME, VirtualClock

    cfg = T.get_test_config(instance)
    cfg.HTTP_PORT = 0
    cfg.DATABASE = f"sqlite3://{tmp_path}/node.db"
    cfg.BUCKET_DIR_PATH = str(tmp_path / "buckets")
    return Application.create(VirtualClock(VIRTUAL_TIME), cfg, new_db=new_db)


def three_closes(app, tag):
    """A ledger that creates six accounts, then two of payments among them
    (one of them failing: a fee row and an empty meta) -> the three closed
    ledgers' hashes."""
    keys = [T.get_account(f"hs-{tag}-{i}") for i in range(6)]
    first = funded(app, keys)
    hashes = [app.ledger_manager.last_closed.hash]
    for n in (1, 2):
        txs = [pay(app, k, first + n, keys[i ^ 1], 100 * n + i) for i, k in enumerate(keys)]
        if n == 2:
            txs[3] = pay(app, keys[3], first + n, keys[2], 10**12)
        close(app, txs)
        hashes.append(app.ledger_manager.last_closed.hash)
    return hashes


# -- the schema ---------------------------------------------------------------


@pytest.fixture
def node_db():
    from test_serial_apply import node

    app, _clock = node(231)
    try:
        yield app
    finally:
        app.graceful_stop()


@pytest.mark.parametrize("table", TABLES)
def test_key_is_ledgerseq_txindex_and_the_only_index(node_db, table):
    key, indexes = key_of(node_db.database, table)
    assert key == ["ledgerseq", "txindex"]
    assert indexes == [["ledgerseq", "txindex"]]


@pytest.mark.parametrize("table", TABLES)
def test_rowid_order_is_key_order_after_three_closes(node_db, table):
    three_closes(node_db, "rowid")
    db = node_db.database
    rows = rows_by_rowid(db, table)
    assert len(rows) == 1 + 6 + 6 and {r[1] for r in rows} == {2, 3, 4}
    assert rows == rows_by_key(db, table)


def test_history_read_uses_the_key_and_no_sort(node_db, monkeypatch):
    """The statement `load_transaction_history` really runs, explained."""
    db = node_db.database
    seen = []
    real = db.query_all
    monkeypatch.setattr(db, "query_all", lambda sql, params=(): seen.append((sql, params)) or real(sql, params))
    tx_history.load_transaction_history(db, 3)
    monkeypatch.undo()
    ((sql, params),) = seen
    plan = " | ".join(row[-1] for row in db.query_all("EXPLAIN QUERY PLAN " + sql, params))
    assert "sqlite_autoindex_txhistory_1 (ledgerseq=?)" in plan
    assert "TEMP B-TREE" not in plan and "SCAN" not in plan


@pytest.mark.parametrize("table", TABLES)
def test_second_row_at_one_place_is_refused(node_db, table):
    three_closes(node_db, "dup")
    db = node_db.database
    row = list(db.query_one(f"SELECT * FROM {table} WHERE ledgerseq=3 AND txindex=2"))
    row[0] = "ab" * 32  # another transaction's hash at the same place
    before = rows_by_key(db, table)
    with pytest.raises(sqlite3.IntegrityError):
        db.execute(f"INSERT INTO {table} VALUES ({','.join('?' * len(row))})", row)
    assert rows_by_key(db, table) == before


# -- the rebuild --------------------------------------------------------------


def synthetic_rows(ledgers=(2, 3, 5, 9), per_ledger=7):
    """Rows of several ledgers in the order the OLD key stored them by: the
    hash's, so neither table's rowid order is (ledgerseq, txindex) order."""
    import hashlib

    tx_rows, fee_rows = [], []
    for seq in ledgers:
        for i in range(1, per_ledger + 1):
            txid = hashlib.sha256(b"%d/%d" % (seq, i)).hexdigest()
            tx_rows.append((txid, seq, i, f"body{seq}.{i}=", f"res{seq}.{i}", "" if i == 3 else f"meta{seq}.{i}"))
            fee_rows.append((txid, seq, i, f"chg{seq}.{i}"))
    return sorted(tx_rows), sorted(fee_rows)


def version_1_database(path):
    db = Database(f"sqlite3://{path}")
    db.initialize()
    to_schema_1(db)
    tx_rows, fee_rows = synthetic_rows()
    tx_history.insert_transaction_rows(db, tx_rows)
    tx_history.insert_fee_rows(db, fee_rows)
    assert rows_by_rowid(db, "txhistory") != rows_by_key(db, "txhistory")
    return db, {"txhistory": sorted(tx_rows, key=lambda r: r[1:3]), "txfeehistory": sorted(fee_rows, key=lambda r: r[1:3])}


def assert_rebuilt(db, want):
    assert db.get_schema_version() == SCHEMA_VERSION == 2
    for table in TABLES:
        assert key_of(db, table) == (["ledgerseq", "txindex"], [["ledgerseq", "txindex"]])
        assert rows_by_rowid(db, table) == want[table]  # byte for byte, in key order
    names = {r[0] for r in db.query_all("SELECT name FROM sqlite_master")}
    assert not names & {"histbyseq", "histfeebyseq", "txhistory_rekeyed", "txfeehistory_rekeyed"}


def assert_still_version_1(db, want):
    assert db.get_schema_version() == 1
    for table in TABLES:
        key, indexes = key_of(db, table)
        assert key == ["txid", "ledgerseq"] and ["ledgerseq"] in indexes
        assert rows_by_key(db, table) == want[table]


def test_version_1_database_is_rebuilt(tmp_path):
    db, want = version_1_database(tmp_path / "v1.db")
    db.upgrade_to_current_schema()
    assert_rebuilt(db, want)
    db.close()
    # and what a fresh connection reads from the file
    db = Database(f"sqlite3://{tmp_path}/v1.db")
    assert_rebuilt(db, want)


REBUILD_STATEMENTS = 9  # four a table and the version's


@pytest.mark.parametrize("after", list(range(REBUILD_STATEMENTS)) + ["db.commit:pre"])
def test_kill_inside_the_rebuild_leaves_version_1_whole(tmp_path, after):
    """A kill after ``after`` of the rebuild's statements (or with all of
    them run and the COMMIT not): the file holds schema 1 and every row,
    and the next open rebuilds it."""
    db, want = version_1_database(tmp_path / "v1.db")
    real, done = db.execute, []

    def execute(sql, params=()):
        if len(done) == after:
            raise fs.SimulatedProcessKill(f"statement {after}")
        done.append(sql)
        return real(sql, params)

    def hook(name, path, ctx):
        if name == after:
            raise fs.SimulatedProcessKill(name, ctx)

    db.execute = execute
    fs.add_kill_hook(hook)
    try:
        with pytest.raises(fs.SimulatedProcessKill):
            db.upgrade_to_current_schema()
    finally:
        fs.remove_kill_hook(hook)
    if after == "db.commit:pre":
        assert len(done) == REBUILD_STATEMENTS
    db.close()

    db = Database(f"sqlite3://{tmp_path}/v1.db")
    assert_still_version_1(db, want)
    db.upgrade_to_current_schema()
    assert_rebuilt(db, want)


def test_process_death_inside_the_rebuild_leaves_version_1_whole(tmp_path):
    """The same with no unwinding at all: the process exits between two of
    the rebuild's statements, no ROLLBACK is run, and the WAL is left as
    it lies."""
    db, want = version_1_database(tmp_path / "v1.db")
    db.close()
    child = textwrap.dedent(
        """
        import os, sys
        from stellar_tpu.database.database import Database
        db = Database("sqlite3://" + sys.argv[1])
        real, done = db.execute, []
        def execute(sql, params=()):
            if len(done) == 6:  # the second table's rows copied, the old one not dropped
                os._exit(9)
            done.append(sql)
            return real(sql, params)
        db.execute = execute
        db.upgrade_to_current_schema()
        """
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rc = subprocess.run([sys.executable, "-c", child, str(tmp_path / "v1.db")], cwd=root).returncode
    assert rc == 9
    db = Database(f"sqlite3://{tmp_path}/v1.db")
    assert_still_version_1(db, want)
    db.upgrade_to_current_schema()
    assert_rebuilt(db, want)


def test_version_2_database_is_not_touched(tmp_path):
    db = Database(f"sqlite3://{tmp_path}/v2.db")
    db.initialize()
    tx_rows, fee_rows = synthetic_rows()
    tx_history.insert_transaction_rows(db, tx_rows)  # rowid order: the hash's
    tx_history.insert_fee_rows(db, fee_rows)
    before = {t: rows_by_rowid(db, t) for t in TABLES}
    statements = []
    db._conn.set_trace_callback(statements.append)
    db.upgrade_to_current_schema()
    db._conn.set_trace_callback(None)
    assert [s for s in statements if not s.lstrip().upper().startswith("SELECT")] == []
    assert {t: rows_by_rowid(db, t) for t in TABLES} == before


def test_database_not_initialized_or_newer(tmp_path):
    db = Database(f"sqlite3://{tmp_path}/empty.db")
    db.upgrade_to_current_schema()  # no storestate table yet: nothing to do
    assert db.query_all("SELECT name FROM sqlite_master") == []
    db.initialize()
    db.put_schema_version(SCHEMA_VERSION + 1)
    with pytest.raises(RuntimeError, match="newer"):
        db.upgrade_to_current_schema()


def test_node_opens_a_version_1_database_at_version_2(tmp_path):
    """Closes on the old tables, a stop, and the node opened again: the
    rebuild runs before anything reads the tables, the rows are what they
    were, and the next close appends to them."""
    app = file_node(tmp_path, 232, new_db=True)
    to_schema_1(app.database)
    hashes = three_closes(app, "reopen")
    want = {t: rows_by_key(app.database, t) for t in TABLES}
    app.graceful_stop()
    app.database.close()

    app = file_node(tmp_path, 232, new_db=False)
    try:
        assert_rebuilt(app.database, want)
        app.start()
        lm = app.ledger_manager
        assert lm.last_closed.hash == hashes[-1]
        assert len(tx_history.load_transaction_history(app.database, 4)) == 6
        keys = [T.get_account(f"hs-reopen-{i}") for i in range(6)]
        close(app, [pay(app, k, (2 << 32) + 3, keys[i ^ 1], 7) for i, k in enumerate(keys)])
        for table in TABLES:
            rows = rows_by_rowid(app.database, table)
            assert rows[: len(want[table])] == want[table] and len(rows) == len(want[table]) + 6
            assert rows == rows_by_key(app.database, table)
    finally:
        app.graceful_stop()


# -- old key and new key: the same node ---------------------------------------


def published_files(archive):
    out = {}
    for path in glob.glob(f"{archive}/**/*", recursive=True):
        if os.path.isfile(path):
            data = open(path, "rb").read()
            out[os.path.relpath(path, archive)] = gzip.decompress(data) if path.endswith(".gz") else data
    return out


def test_old_and_new_key_close_and_publish_the_same(tmp_path):
    """The same three closes on schema 1 and on schema 2: the same ledger
    hashes, the same rows in both tables, the same checkpoint files."""
    from test_history import archive_config

    from stellar_tpu.main.application import Application
    from stellar_tpu.util.clock import REAL_TIME, VirtualClock

    got = {}
    for schema in (1, 2):
        archive = tmp_path / f"archive{schema}"
        archive.mkdir()
        cfg = T.get_test_config(233)
        cfg.HTTP_PORT = 0
        cfg.CHECKPOINT_FREQUENCY = 4  # ledgers 2, 3 and the genesis: one checkpoint
        cfg.HISTORY = archive_config(str(archive), True)
        shutil.rmtree(cfg.BUCKET_DIR_PATH, ignore_errors=True)
        clock = VirtualClock(REAL_TIME)
        app = Application.create(clock, cfg, new_db=True)
        try:
            if schema == 1:
                to_schema_1(app.database)
            app.start()
            hashes = three_closes(app, "same")
            assert clock.crank_until(lambda: app.history_manager.get_publish_success_count() > 0, 30)
            got[schema] = (
                hashes,
                {t: set(rows_by_key(app.database, t)) for t in TABLES},
                published_files(str(archive)),
                key_of(app.database, "txhistory")[0],
            )
        finally:
            app.graceful_stop()
            clock.shutdown()
    assert got[1][3] == ["txid", "ledgerseq"] and got[2][3] == ["ledgerseq", "txindex"]
    assert got[1][0] == got[2][0]
    assert got[1][1] == got[2][1] and len(got[1][1]["txhistory"]) == 13
    names = sorted(got[2][2])
    assert any(n.startswith("transactions/") for n in names) and any(n.startswith("results/") for n in names)
    assert got[1][2] == got[2][2]
