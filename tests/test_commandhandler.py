"""Admin command surface (reference: src/main/CommandHandler.cpp route
table at :62-92 and the testAcc/testTx handlers at :117-231).

Routes are exercised through the handler's dispatch table (the HTTP
plumbing itself is covered by the live-node drive in the verify recipe);
one end-to-end case drives a create-account transaction through /testtx,
closes a ledger, and reads the result back through /testacc.
"""

from __future__ import annotations

import pytest

from stellar_tpu.main.application import Application
from stellar_tpu.tx import testutils as T
from stellar_tpu.util.clock import VIRTUAL_TIME, VirtualClock

EXPECTED_ROUTES = {
    # reference CommandHandler.cpp:62-92 (this snapshot has no 'stop')
    "catchup", "checkdb", "checkpoint", "connect", "dropcursor",
    "generateload", "info", "ll", "logrotate", "maintenance",
    "manualclose", "metrics", "peers", "setcursor", "scp",
    "testacc", "testtx", "tx",
    # TPU-native extras beyond the reference's table
    "profiler", "trace", "invariants", "selfcheck", "ingest",
}


@pytest.fixture
def app():
    clock = VirtualClock(VIRTUAL_TIME)
    cfg = T.get_test_config(80)
    cfg.MANUAL_CLOSE = True
    cfg.HTTP_PORT = 0  # dispatch-table tests; no socket needed
    a = Application.create(clock, cfg, new_db=True)
    a.start()  # FORCE_SCP from the test config bootstraps the herder
    yield a
    a.graceful_stop()
    clock.shutdown()


def test_route_table_matches_reference(app):
    assert set(app.command_handler.routes) == EXPECTED_ROUTES


def test_info_metrics_scp(app):
    ch = app.command_handler
    info = ch.handle_info({})["info"]
    assert info["ledger"]["num"] == 1
    assert info["network"] == app.config.NETWORK_PASSPHRASE
    # the backend's name and the process-wide count of eager verifies
    sb = info["sig_backend"]
    assert set(sb) == {"backend", "eager_host_verifies"} and sb["backend"] == "cpu"
    assert sb["eager_host_verifies"] >= 0
    # a close is applied by one loop: /info has nothing to say about which
    assert "apply" not in info
    # the order book's work, the transactions that failed at apply and the
    # PAYMENTs that went through credit / debit, since the node started
    assert info["exchange"] == {
        "conversions": 0, "offers_crossed": 0, "book_pages": 0, "book_rows": 0, "book_side_loads": 0,
        "txs_failed_at_apply": 0, "payments_applied": 0,
    }
    # the decoded-entry cache: genesis stored the root account as a line;
    # nothing was evicted, warmed or asked of SQL yet
    ec = info["entry_cache"]
    assert ec == {
        "hits": ec["hits"], "misses": ec["misses"], "evictions": 0, "warm_asked": 0, "sql_loads": 0,
        "lines": 1, "capacity": 131072,
    }
    assert info["history"]["bucket_apply_entries"] == 0 and info["history"]["bucket_apply_s"] == 0.0
    assert "metrics" in ch.handle_metrics({})
    assert isinstance(ch.handle_scp({}), dict)


def test_info_reports_what_runs_the_tpu_backend():
    """/info's sig_backend block names the device JAX found, the kernel
    lowering and whether it is interpreted — a tpu-backend node on a CPU
    must say so, not just "tpu"."""
    import json

    clock = VirtualClock(VIRTUAL_TIME)
    cfg = T.get_test_config(83, backend="tpu")
    cfg.HTTP_PORT = 0
    a = Application.create(clock, cfg, new_db=True)
    try:
        sb = a.command_handler.handle_info({})["info"]["sig_backend"]
        json.dumps(sb)  # the route serializes it
        assert sb["backend"] == "tpu"
        assert sb["platform"] == "cpu" and sb["device_count"] >= 1
        assert sb["device_kind"]
        assert sb["kernel"] == "xla" and sb["interpret"] is False
        assert sb["native_host_stage"] in (True, False)
        assert sb["device_calls"] == 0 and sb["cpu_cutover_items"] == 0
        assert sb["wedge_fallback_items"] == 0
        assert sb["wedge_latch_flips"] == {}
    finally:
        a.graceful_stop()
        clock.shutdown()


def test_testacc_root_and_missing(app):
    ch = app.command_handler
    out = ch.handle_testacc({"name": "root"})
    assert out["balance"] > 0 and out["seqnum"] >= 0
    # named-but-never-created account: id resolves, no balance fields
    out = ch.handle_testacc({"name": "bob"})
    assert out["id"].startswith("G") or len(out["id"]) > 30
    assert "balance" not in out
    assert ch.handle_testacc({})["status"] == "error"


def test_testtx_creates_account_through_consensus(app):
    ch = app.command_handler
    lm = app.ledger_manager
    out = ch.handle_testtx(
        {"from": "root", "to": "bob", "amount": str(10**10), "create": "true"}
    )
    assert out["status"] == "PENDING", out
    # manual close externalizes the pending tx
    target = lm.get_last_closed_ledger_num() + 1
    app.herder.trigger_next_ledger(lm.get_ledger_num())
    assert app.clock.crank_until(
        lambda: lm.get_last_closed_ledger_num() >= target, 30
    )
    acc = ch.handle_testacc({"name": "bob"})
    assert acc["balance"] == 10**10
    # then a plain payment back
    out = ch.handle_testtx({"from": "bob", "to": "root", "amount": "12345"})
    assert out["status"] == "PENDING", out
    target += 1
    app.herder.trigger_next_ledger(lm.get_ledger_num())
    assert app.clock.crank_until(
        lambda: lm.get_last_closed_ledger_num() >= target, 30
    )
    acc = ch.handle_testacc({"name": "bob"})
    assert acc["balance"] == 10**10 - 12345 - 100  # amount + base fee


def test_testtx_missing_params(app):
    out = app.command_handler.handle_testtx({"from": "root"})
    assert out["status"] == "error"


def test_two_testtx_in_one_ledger_window(app):
    """Sequence numbers must account for herder-pending txs: two testtx
    submissions from root before a close both go PENDING (review finding;
    the reference testTx shares the bug — we fix it)."""
    ch = app.command_handler
    out1 = ch.handle_testtx(
        {"from": "root", "to": "bob", "amount": "100000000", "create": "true"}
    )
    out2 = ch.handle_testtx(
        {"from": "root", "to": "alice", "amount": "100000000", "create": "true"}
    )
    assert (out1["status"], out2["status"]) == ("PENDING", "PENDING")


def test_get_account_matches_reference_seed_stretch():
    """TxTests.cpp:200-208: the seed for a named account is the name
    padded to 32 bytes with '.' — byte-for-byte."""
    from stellar_tpu.crypto.keys import SecretKey

    want = SecretKey.from_seed(b"bob" + b"." * 29)
    assert T.get_account("bob").get_public_key() == want.get_public_key()


def test_logrotate_reopens_file(app, tmp_path):
    """LOG_FILE_PATH + /logrotate: after an external move, logging resumes
    into a fresh file at the configured path."""
    import os

    from stellar_tpu.util import xlog

    path = str(tmp_path / "node.log")
    xlog.add_file(path)
    try:
        log = xlog.logger("test")
        log.error("before rotate")
        os.rename(path, path + ".1")
        out = app.command_handler.handle_logrotate({})
        assert out == {"status": "ok", "rotated": True}
        log.error("after rotate")
        assert os.path.exists(path)
        assert "after rotate" in open(path).read()
        assert "before rotate" in open(path + ".1").read()
    finally:
        import logging

        xlog._file_path = ""
        if xlog._file_handler is not None:
            logging.getLogger("stellar_tpu").removeHandler(xlog._file_handler)
            xlog._file_handler.close()
            xlog._file_handler = None


def test_profiler_route(app, tmp_path):
    """/profiler start/stop wraps jax.profiler tracing (SURVEY.md §5.1)."""
    import os

    ch = app.command_handler
    d = str(tmp_path / "trace")
    r = ch.handle_profiler({"action": "start", "dir": d})
    assert r.get("status") == "profiling", r
    assert "error" in ch.handle_profiler({"action": "start"})  # double start
    r = ch.handle_profiler({"action": "stop"})
    assert r.get("status") == "stopped", r
    assert os.path.isdir(d) and os.listdir(d), "trace dir must be written"
    assert "error" in ch.handle_profiler({"action": "stop"})  # not running
    assert "error" in ch.handle_profiler({})  # bad action


def test_profiler_writes_the_markers_that_join_it_to_trace(app, tmp_path):
    """An operator can lay /trace over /profiler: the profile's host plane
    holds a ``trace.sync.<time.monotonic_ns()>`` annotation from right
    after the start and one from right before the stop; /trace names the
    clock of its own timestamps."""
    import glob
    import time

    from jax.profiler import ProfileData

    ch = app.command_handler
    d = str(tmp_path / "trace")
    t0 = time.monotonic_ns()
    assert ch.handle_profiler({"action": "start", "dir": d}).get("status") == "profiling"
    with app.tracer.span("demo.phase"):
        pass
    assert ch.handle_profiler({"action": "stop"}).get("status") == "stopped"
    t1 = time.monotonic_ns()
    (path,) = glob.glob(d + "/plugins/profile/*/*.xplane.pb")
    marks = [
        (int(e.name[len("trace.sync."):]), e.start_ns)
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
        if e.name.startswith("trace.sync.")
    ]
    assert len(marks) == 2
    assert all(t0 <= ns <= t1 for ns, _ in marks)
    # one offset, both times: the profiler's clock less time.monotonic
    (a_ns, a_at), (b_ns, b_at) = sorted(marks)
    assert abs((a_at - a_ns) - (b_at - b_ns)) < 5e6  # within 5 ms of each other
    # (this fixture's node runs on the virtual clock, and /trace says so;
    # a node on the real clock says "monotonic": tests/test_trace.py)
    out = ch.execute("/trace")
    assert out["clock"] == app.tracer.clock_name == "virtual"
    assert any(e["name"] == "demo.phase" for e in out["traceEvents"])


def test_maintenance_queue_processing():
    """HerderTests.cpp:103-147 'Queue processing': pubsub cursors gate
    maintenance deletion of old ledger headers; the min across cursors
    (and the publish checkpoint window) controls what is trimmed.  A
    small CHECKPOINT_FREQUENCY keeps the consensus rounds cheap."""
    from stellar_tpu.ledger.headerframe import LedgerHeaderFrame

    clock = VirtualClock(VIRTUAL_TIME)
    cfg = T.get_test_config(85)
    cfg.MANUAL_CLOSE = True
    cfg.HTTP_PORT = 0
    cfg.CHECKPOINT_FREQUENCY = 8
    app = Application.create(clock, cfg, new_db=True)
    app.start()
    ch = app.command_handler
    lm = app.ledger_manager
    # close ledgers past a checkpoint window so the publish bound allows
    # deletion up to the cursors
    freq = app.history_manager.checkpoint_frequency
    while lm.get_last_closed_ledger_num() < freq + 5:
        target = lm.get_last_closed_ledger_num() + 1
        app.herder.trigger_next_ledger(lm.get_ledger_num())
        assert app.clock.crank_until(
            lambda: lm.get_last_closed_ledger_num() >= target, 30
        )
        # closeTime advances +1s per close; keep the virtual clock in step
        # (the reference's crank(true) cadence advances time the same way)
        app.clock.crank_for(1.0)

    db = app.database
    ch.execute("setcursor?id=A1&cursor=1")
    ch.execute("maintenance?queue=true")
    ch.execute("setcursor?id=A2&cursor=3")
    ch.execute("maintenance?queue=true")
    # min cursor is 1: header 2 must survive
    assert LedgerHeaderFrame.load_by_sequence(db, 2) is not None

    ch.execute("setcursor?id=A1&cursor=2")
    ch.execute("maintenance?queue=true")  # deletes <= 2
    assert LedgerHeaderFrame.load_by_sequence(db, 2) is None
    assert LedgerHeaderFrame.load_by_sequence(db, 3) is not None

    # min to 3 by dropping the lower cursor
    ch.execute("dropcursor?id=A1")
    ch.execute("maintenance?queue=true")  # min now A2=3
    assert LedgerHeaderFrame.load_by_sequence(db, 3) is None
    app.graceful_stop()
    clock.shutdown()
