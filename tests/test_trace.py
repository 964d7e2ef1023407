"""stellar_tpu/trace/ — span tracer, ring buffer, Chrome export, aggregator,
end-to-end close-phase attribution, and the hot-path overhead contract."""

from __future__ import annotations

import json
import time

import pytest

from stellar_tpu.trace import NULL_TRACER, Tracer, tracer_of
from stellar_tpu.util import VIRTUAL_TIME, VirtualClock


@pytest.fixture()
def clock():
    c = VirtualClock(VIRTUAL_TIME)
    yield c
    c.shutdown()


class TestTracerCore:
    def test_deterministic_timestamps_under_virtual_clock(self, clock):
        """Spans stamped off a VIRTUAL clock are bit-for-bit reproducible:
        the trace of a simulation test is a stable artifact."""
        tr = Tracer(clock=clock)
        clock.set_current_virtual_time(10.0)
        sp = tr.begin("phase.one", k=1)
        clock.set_current_virtual_time(12.5)
        tr.end(sp)
        with tr.span("phase.two"):
            clock.set_current_virtual_time(13.0)
        spans = tr.spans()
        assert [(s.name, s.start, s.end) for s in spans] == [
            ("phase.one", 10.0, 12.5),
            ("phase.two", 12.5, 13.0),
        ]
        # and the Chrome export inherits the determinism (µs scale)
        ev = tr.chrome_trace()["traceEvents"]
        assert ev[0]["ts"] == 10_000_000.0 and ev[0]["dur"] == 2_500_000.0

    def test_real_time_clock_falls_back_to_monotonic(self):
        """A REAL_TIME clock's now() is wall time (can step backwards);
        traces must use the monotonic fallback instead."""
        from stellar_tpu.util.clock import REAL_TIME

        c = VirtualClock(REAL_TIME)
        try:
            tr = Tracer(clock=c)
            t0 = time.monotonic()
            with tr.span("x"):
                pass
            (sp,) = tr.spans()
            assert abs(sp.start - t0) < 5.0  # monotonic scale, not unix epoch
            assert sp.end >= sp.start
        finally:
            c.shutdown()

    def test_ring_wraparound(self, clock):
        tr = Tracer(clock=clock, ring_size=4)
        for i in range(10):
            with tr.span(f"s.{i}"):
                pass
        spans = tr.spans()
        assert [s.name for s in spans] == ["s.6", "s.7", "s.8", "s.9"]
        assert tr.dropped == 6
        # aggregates survive the wraparound: every completed span counted
        assert sum(a["count"] for a in tr.aggregates().values()) == 10
        tr.clear()
        assert tr.spans() == [] and tr.dropped == 0

    def test_chrome_json_schema(self, clock):
        tr = Tracer(clock=clock)
        clock.set_current_virtual_time(1.0)
        sp = tr.begin("a.b", blob=b"\x01\x02", n=3, label="x")
        clock.set_current_virtual_time(2.0)
        tr.end(sp)
        out = tr.chrome_trace()
        payload = json.loads(json.dumps(out))  # must be JSON-serializable
        assert payload["displayTimeUnit"] == "ms"
        (ev,) = payload["traceEvents"]
        for key in ("name", "cat", "ph", "ts", "dur", "pid", "tid"):
            assert key in ev
        assert ev["ph"] == "X"
        assert ev["cat"] == "a"
        # the span's own attributes, beside what names its cause
        assert ev["args"] == {"sid": 1, "blob": "0102", "n": 3, "label": "x"}
        assert payload["clock"] == "virtual"
        assert Tracer().chrome_trace()["clock"] == "monotonic"

    def test_aggregator_percentiles(self, clock):
        tr = Tracer(clock=clock)
        t = 0.0
        for ms in range(1, 101):  # 1..100 ms spans
            sp = tr.begin("work")
            t += ms / 1000.0
            clock.set_current_virtual_time(t)
            tr.end(sp)
        agg = tr.aggregates()["work"]
        assert agg["count"] == 100
        assert agg["max_ms"] == pytest.approx(100.0)
        assert agg["p50_ms"] == pytest.approx(50.5)  # interpolated median
        assert agg["p95_ms"] == pytest.approx(95.05, rel=1e-3)
        assert agg["p50_ms"] <= agg["p95_ms"] <= agg["max_ms"]
        # the same aggregate is visible through a shared MetricsRegistry
        from stellar_tpu.util.metrics import MetricsRegistry

        m = MetricsRegistry()
        tr2 = Tracer(clock=clock, metrics=m)
        with tr2.span("x.y"):
            pass
        assert m.to_json()["trace.x.y"]["count"] == 1

    def test_disabled_tracer_records_nothing(self, clock):
        tr = Tracer(enabled=False, clock=clock)
        with tr.span("a", k=1):
            pass
        tr.end(tr.begin("b"))
        assert tr.spans() == []
        assert tr.aggregates() == {}
        assert tr.chrome_trace()["traceEvents"] == []
        # the app-less fallback is the same disabled object
        class _Stub:
            pass

        assert tracer_of(_Stub()) is NULL_TRACER
        assert NULL_TRACER.spans() == []

    def test_end_is_none_safe_and_double_end_safe(self, clock):
        tr = Tracer(clock=clock)
        tr.end(None)  # disabled-begin result
        sp = tr.begin("x")
        tr.end(sp)
        tr.end(sp)  # double end must not double-record
        assert len(tr.spans()) == 1

    def test_threaded_recording(self, clock):
        import threading

        tr = Tracer(clock=clock, ring_size=4096)

        def work():
            for _ in range(200):
                with tr.span("t"):
                    pass

        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert tr.aggregates()["t"]["count"] == 800


def _tree(spans):
    """(name, parent's name, req, start, end) of every span, by sid."""
    by = {s.sid: s for s in spans}
    return [
        (s.sid, s.name, by[s.parent].name if s.parent in by else s.parent, s.req, s.start, s.end)
        for s in sorted(spans, key=lambda s: s.sid)
    ]


class TestCause:
    """Every span names its cause: ``sid``, ``parent``, ``req``."""

    def test_parent_sid_req_on_one_thread(self, clock):
        tr = Tracer(clock=clock)
        with tr.span("ledger.close", req=7, seq=7) as root:
            assert tr.current() is root
            a = tr.begin("close.fees")
            with tr.span("fees.charge") as inner:
                assert (inner.parent, inner.req) == (a.sid, 7)
            tr.end(a, txs=3)
            with tr.span("close.apply", req=99) as b:
                # the parent's request wins: a span is part of the request
                # that caused it
                assert (b.parent, b.req) == (root.sid, 7)
        assert tr.current() is None
        with tr.span("alone") as lone:
            assert (lone.parent, lone.req) == (None, None)
        sids = [s.sid for s in sorted(tr.spans(), key=lambda s: s.sid)]
        assert sids == [1, 2, 3, 4, 5]  # per tracer, from 1, in order of beginning
        assert Tracer(clock=clock).begin("x").sid == 1
        ev = {e["name"]: e["args"] for e in tr.chrome_trace()["traceEvents"]}
        assert ev["fees.charge"] == {"sid": 3, "parent": 2, "req": 7}
        assert ev["close.fees"] == {"sid": 2, "parent": 1, "req": 7, "txs": 3}
        assert ev["alone"] == {"sid": 5}

    def test_end_unwinds_what_an_exception_left_open(self, clock):
        tr = Tracer(clock=clock)
        with pytest.raises(RuntimeError):
            with tr.span("outer"):
                tr.begin("never.ended")  # its end is skipped by the raise
                raise RuntimeError
        assert tr.current() is None
        assert [s.name for s in tr.spans()] == ["outer"]

    def test_detached_span_has_a_parent_and_is_nobodys(self, clock):
        tr = Tracer(clock=clock)
        with tr.span("scp.envelope") as cause:
            fetch = tr.begin("overlay.fetch", detached=True)
            with tr.span("next") as nxt:
                assert nxt.parent == cause.sid
        assert fetch.parent == cause.sid
        assert tr.current() is None  # the open fetch is on no stack
        with tr.span("later") as later:
            assert later.parent is None
        tr.end(fetch)

    def test_explicit_hand_off_across_threads(self, clock):
        import threading

        tr = Tracer(clock=clock)
        seen = {}

        def leg(parent):
            sp = tr.begin("worker.leg", parent=parent)
            with tr.span("tx.apply") as t:
                seen["tx"] = (t.parent, t.req)
            tr.end(sp)
            seen["leg"] = (sp.parent, sp.req)
            seen["after"] = tr.current()

        def worker(parent):
            with tr.under(parent):
                with tr.span("ed25519.drain") as d:
                    seen["drain"] = (d.parent, d.req)
            seen["worker_after"] = tr.current()

        with tr.span("close.apply", req=12) as ca:
            for fn in (leg, worker):
                t = threading.Thread(target=fn, args=(tr.current(),))
                t.start()
                t.join()
            assert tr.current() is ca  # the other threads' stacks are theirs
        handed = next(s for s in tr.spans() if s.name == "worker.leg")
        assert seen["leg"] == (ca.sid, 12)
        assert seen["tx"] == (handed.sid, 12)
        assert seen["drain"] == (ca.sid, 12)
        assert seen["after"] is None and seen["worker_after"] is None
        # a thread with nothing handed to it starts from nothing
        with tr.under(None):
            assert tr.current() is None

    def test_span_block_takes_a_parent_and_ends_with_what_the_body_learned(self, clock):
        import threading

        tr = Tracer(clock=clock)
        seen = {}

        def leg(parent):
            with tr.span("worker.leg", parent=parent, txs=2) as sp:
                seen["leg"] = (sp.parent, sp.req)
                tr.end(sp, done=2)  # the block's exit is then a no-op
            seen["after"] = tr.current()

        with tr.span("close.apply", req=9) as ca:
            t = threading.Thread(target=leg, args=(tr.current(),))
            t.start()
            t.join()
        assert seen == {"leg": (ca.sid, 9), "after": None}
        (handed,) = [s for s in tr.spans() if s.name == "worker.leg"]
        assert handed.attrs == {"txs": 2, "done": 2}
        assert tr.aggregates()["worker.leg"]["count"] == 1  # ended once

    @pytest.mark.parametrize("site", ["sig.flush", "ingest.flush", "apply.serial"])
    def test_a_raising_body_leaves_no_stale_parent(self, clock, site, monkeypatch):
        """The spans that sit at the bottom of a long-lived thread's stack
        are ended on the failure path too: what the thread records next
        has no parent."""
        from stellar_tpu.crypto.sigbackend import CachingSigBackend
        from stellar_tpu.crypto.sigcache import VerifySigCache

        class Boom(Exception):
            pass

        class Raising:
            name = "raising"

            def verify_batch(self, items, caller=None):
                raise Boom

        item = (b"\x01" * 32, b"msg", b"\x02" * 64)
        if site == "sig.flush":
            tr = Tracer(clock=clock)
            be = CachingSigBackend(Raising(), VerifySigCache(), tracer=tr)
            with pytest.raises(Boom):
                be.verify_batch([item])
        elif site == "ingest.flush":
            from stellar_tpu.ledger.accountframe import AccountFrame
            from stellar_tpu.main.application import Application
            from stellar_tpu.tx import testutils as T

            cfg = T.get_test_config(179)
            cfg.HTTP_PORT = 0
            app = Application.create(clock, cfg, new_db=True)
            try:
                app.start()
                tr = app.tracer
                root = T.root_key_for(app)
                seq = AccountFrame.load_account(root.get_public_key(), app.database).get_seq_num()
                tx = T.tx_from_ops(app, root, seq + 1, [T.create_account_op(T.get_account(9900), 10**9)])
                app.ingest._inner = Raising()
                tr.clear()
                with pytest.raises(Boom):
                    app.ingest.submit_sync(tx)
            finally:
                app.graceful_stop()
        else:
            # a transaction whose apply could not be unwound aborts the
            # close from inside apply.serial, on the closing thread
            from stellar_tpu.database.database import UnrollbackableWrite
            from stellar_tpu.main.application import Application
            from stellar_tpu.tx import testutils as T
            from stellar_tpu.tx.frame import TransactionFrame

            app = Application(clock, T.get_test_config(175), new_db=True)
            try:
                tr = app.tracer
                lm = app.ledger_manager
                root = T.root_key_for(app)
                tx = T.tx_from_ops(app, root, 1, [T.create_account_op(T.get_account(9901), 10**9)])

                def apply(self, delta, app_, meta=None, tracer=None):
                    raise UnrollbackableWrite("rows written under no savepoint")

                monkeypatch.setattr(TransactionFrame, "apply", apply)
                tr.clear()
                with pytest.raises(UnrollbackableWrite):
                    T.close_ledger_on(app, lm.last_closed.header.scpValue.closeTime + 5, [tx])
                (close,) = [s for s in tr.spans() if s.name == "ledger.close"]
                assert close.attrs["failed"] is True
            finally:
                app.database.close()
        assert tr.current() is None
        assert site in [s.name for s in tr.spans()]  # ended, so recorded
        with tr.span("next") as nxt:
            assert nxt.parent is None

    def test_identical_trees_under_the_virtual_clock(self):
        def run():
            c = VirtualClock(VIRTUAL_TIME)
            try:
                tr = Tracer(clock=c)
                for seq in (2, 3):
                    c.set_current_virtual_time(float(seq))
                    with tr.span("ledger.close", req=seq):
                        with tr.span("close.apply"):
                            for i in range(130):
                                if not i & 63:
                                    sp = tr.begin("tx.apply", index=i)
                                    c.set_current_virtual_time(seq + (i + 1) / 1000.0)
                                    tr.end(sp)
                        det = tr.begin("scp.ballot", detached=True)
                        with tr.span("close.commit"):
                            pass
                        tr.end(det)
                return _tree(tr.spans()), json.dumps(tr.chrome_trace(), sort_keys=True)
            finally:
                c.shutdown()

        first, second = run(), run()
        assert first == second
        assert len(first[0]) == 2 * (3 + 3 + 1)

    def test_disabled_tracer_hands_nothing_over(self, clock):
        tr = Tracer(enabled=False, clock=clock)
        assert tr.current() is None
        with tr.under(None):
            assert tr.begin("x", parent=None, req=1, detached=True) is None
        assert tr.spans() == []


class TestSelfTime:
    def test_self_time_is_duration_less_the_union_of_children(self):
        from stellar_tpu.trace import Span, self_p50_ms, self_times

        def span(sid, name, t0, t1, parent=None, tid=1):
            sp = Span(name, t0, tid, None, sid, parent)
            sp.end = t1
            return sp

        # two worker legs overlapping in time (other threads), one child that
        # outlives the parent, a grandchild that must not count twice,
        # and a span still open (left out)
        spans = [
            span(1, "close.apply", 0.0, 10.0),
            span(2, "worker.leg", 1.0, 4.0, parent=1, tid=2),
            span(3, "worker.leg", 3.0, 6.0, parent=1, tid=3),
            span(4, "tx.apply", 1.0, 2.0, parent=2, tid=2),
            span(5, "apply.rows", 9.0, 12.0, parent=1),
            Span("open", 0.0, 1, None, 6, 1),
        ]
        st = self_times(spans)
        assert st == {
            1: pytest.approx(10.0 - 5.0 - 1.0),  # children cover [1,6] and [9,10]
            2: pytest.approx(2.0),
            3: pytest.approx(3.0),
            4: pytest.approx(1.0),
            5: pytest.approx(3.0),
        }
        p50 = self_p50_ms(spans)
        assert p50["worker.leg"] == pytest.approx(2500.0)
        assert p50["close.apply"] == pytest.approx(4000.0)
        assert "open" not in p50

    def test_trace_route_reports_self_p50_beside_p50(self, clock):
        from stellar_tpu.main.application import Application
        from stellar_tpu.tx import testutils as T

        cfg = T.get_test_config(174)
        cfg.HTTP_PORT = 0
        app = Application.create(clock, cfg, new_db=True)
        try:
            clock.set_current_virtual_time(100.0)
            with app.tracer.span("outer.phase"):
                clock.set_current_virtual_time(101.0)
                with app.tracer.span("inner.phase"):
                    clock.set_current_virtual_time(103.0)
                clock.set_current_virtual_time(104.0)
            out = app.command_handler.execute("/trace")
            assert out["clock"] == "virtual"
            agg = out["aggregates"]
            assert agg["outer.phase"]["p50_ms"] == pytest.approx(4000.0)
            assert agg["outer.phase"]["self_p50_ms"] == pytest.approx(2000.0)
            assert agg["inner.phase"]["self_p50_ms"] == pytest.approx(2000.0)
        finally:
            app.graceful_stop()


NEW_CLOSE_SPANS = {
    "commit.flush", "commit.invariants", "commit.buckets", "commit.sql",
    "fees.charge", "fees.rows", "apply.serial", "apply.rows",
    "tx.apply", "tx.valid", "tx.ops",
    # PR 26: the prefetch's collection, inside the close where the set was
    # not validated first (as here), else inside txset.validate
    "sig.collect",
    # PR 41: the bulk load of the set's accounts into the entry cache;
    # PR 43: two a close that was not validated first (as here) — the
    # close's ask, which loads, and the collect's, which finds every line
    "accounts.warm",
    # PR 49: the set laid out in the protocol's apply order, once a close
    "txset.sort_for_apply",
}
CLOSE_TXS = 130
# traffic -> (signatures an envelope, keys check_signature walks for it)
TRAFFIC = {"payments": (1, 1), "multisig": (3, 5), "with-failures": (1, 1)}


def _failing(traffic, i):
    """with-failures: every eighth payment is for more than its source holds."""
    return traffic == "with-failures" and i % 8 == 0


@pytest.fixture(scope="module", params=sorted(TRAFFIC))
def traced_closes(request):
    """Three consecutive real-clock closes of 130 payments each (accounts
    in groups of two), traced, through the one loop a close has: -> the
    spans of each close.  The traffic differs: "payments" is one signature
    by the master key; "multisig" holds every account under five signers
    of weight 1 at thresholds 3 and master weight 0, three of them signing
    each envelope (what `multisig5000.close` runs); "with-failures" makes
    every eighth payment underfunded."""
    from test_serial_apply import hold_under_signers, sign_with

    from stellar_tpu.ledger.accountframe import AccountFrame
    from stellar_tpu.main.application import Application
    from stellar_tpu.tx import testutils as T
    from stellar_tpu.util.clock import REAL_TIME

    traffic = request.param
    c = VirtualClock(REAL_TIME)
    app = Application(c, T.get_test_config(176), new_db=True)
    try:
        lm = app.ledger_manager

        def close(txs):
            T.close_ledger_on(app, lm.last_closed.header.scpValue.closeTime + 5, txs)

        root = T.root_key_for(app)
        keys = [T.get_account(7000 + i) for i in range(CLOSE_TXS)]
        seq = AccountFrame.load_account(root.get_public_key(), app.database).get_seq_num()
        close([
            T.tx_from_ops(app, root, seq + 1 + j, [T.create_account_op(k, 10**9) for k in keys[i : i + 70]])
            for j, i in enumerate(range(0, CLOSE_TXS, 70))
        ])
        first = lm.last_closed.header.ledgerSeq << 32
        signers = None
        if traffic == "multisig":
            signers = [[T.get_account(8000 + 5 * i + j) for j in range(5)] for i in range(CLOSE_TXS)]
            held = [hold_under_signers(app, k, first + 1, mine) for k, mine in zip(keys, signers)]
            close(held)
            assert all(tx.get_result_code().name == "txSUCCESS" for tx in held)
            first += 1
        closes = []
        for r in range(3):
            app.tracer.clear()
            pay = [
                T.tx_from_ops(
                    app, k, first + 1 + r,
                    [T.payment_op(keys[i ^ 1], 10**12 if _failing(traffic, i) else 100)],
                )
                for i, k in enumerate(keys)
            ]
            if signers is not None:
                for i, tx in enumerate(pay):
                    sign_with(tx, [signers[i][(i + r + 2 * j) % 5] for j in range(3)])
            close(pay)
            assert [tx.get_result_code().name for tx in pay] == [
                "txFAILED" if _failing(traffic, i) else "txSUCCESS" for i in range(CLOSE_TXS)
            ]
            closes.append((lm.last_closed.header.ledgerSeq, app.tracer.spans()))
        assert app.tracer.dropped == 0
        assert app.invariants.total_violations == 0, app.invariants.dump_info()
        yield traffic, closes
    finally:
        app.database.close()
        c.shutdown()


class TestCloseFromInside:
    """The three spans that held 70 % of a close have children."""

    @pytest.mark.parametrize("phase", ["close.commit", "close.fees", "close.apply"])
    def test_direct_children_cover_the_phase(self, traced_closes, phase):
        _traffic, closes = traced_closes
        shares = []
        for _seq, spans in closes:
            (sp,) = [s for s in spans if s.name == phase]
            kids = [(s.start, s.end) for s in spans if s.parent == sp.sid]
            assert kids, phase
            covered, cursor = 0.0, sp.start
            for lo, hi in sorted(kids):
                lo, hi = max(lo, cursor), min(hi, sp.end)
                if hi > lo:
                    covered += hi - lo
                    cursor = hi
            shares.append(covered / sp.duration)
        # the calmest of the closes
        assert max(shares) >= 0.95, (phase, shares)

    def test_children_by_name(self, traced_closes):
        _traffic, closes = traced_closes
        _seq, spans = closes[1]
        by = {s.sid: s for s in spans}
        kids = {}
        for s in spans:
            if s.parent in by:
                kids.setdefault(by[s.parent].name, set()).add(s.name)
        assert kids["close.commit"] == {
            "commit.flush", "commit.invariants", "commit.buckets", "commit.sql",
        }
        assert all(n.startswith("invariant.") for n in kids["commit.invariants"])
        assert kids["close.fees"] == {"fees.charge", "fees.rows"}
        assert "fees.charge" not in kids  # no span a transaction in the fee loop
        assert kids["close.apply"] == {"apply.serial", "apply.rows"}
        assert kids["apply.serial"] == {"tx.apply"}
        assert kids["tx.apply"] == {"tx.valid", "tx.ops"}

    def test_the_spans_count_the_set_its_signatures_and_its_keys(self, traced_closes):
        traffic, closes = traced_closes
        sigs, keys = TRAFFIC[traffic]
        for _seq, spans in closes:
            # the set, how many of it failed at apply (fee kept, effects unwound)
            # and how many PAYMENTs went through credit / debit (the failing too)
            failed = sum(1 for i in range(CLOSE_TXS) if _failing(traffic, i))
            assert [s.attrs for s in spans if s.name == "apply.serial"] == [
                {"txs": CLOSE_TXS, "accounts": CLOSE_TXS, "failed": failed, "payments": CLOSE_TXS}
            ]
            # the sample says which operation it timed
            assert {s.attrs["op"] for s in spans if s.name == "tx.apply"} == {"PAYMENT"}
            assert [s.attrs for s in spans if s.name == "apply.rows"] == [{"rows": CLOSE_TXS}]
            # the fee pass: every transaction has a source of its own, the
            # pool is raised on one copy of the header, a row a transaction
            assert [s.attrs for s in spans if s.name == "fees.charge"] == [
                {"txs": CLOSE_TXS, "accounts": CLOSE_TXS, "header_copies": 1}
            ]
            assert [s.attrs for s in spans if s.name == "fees.rows"] == [{"rows": CLOSE_TXS}]
            # one collection a close; every signature's hint finds one key
            assert [s.attrs for s in spans if s.name == "sig.collect"] == [{
                "txs": CLOSE_TXS, "signatures": sigs * CLOSE_TXS,
                "triples": sigs * CLOSE_TXS, "accounts": CLOSE_TXS,
            }]
            # which way check_signature went under the sampled tx.valid:
            # the general signer loop where the account is held by signers
            valid = [s.attrs for s in spans if s.name == "tx.valid"]
            assert valid == [{"sigs": sigs, "keys": keys}] * 3

    @pytest.mark.parametrize(
        "fee_set", ["two-sources-of-five", "zero-fees", "empty-set", "nested-header-a-transaction"]
    )
    def test_fee_pass_counts_its_accounts_and_its_header_copies(self, clock, fee_set, monkeypatch):
        """`fees.charge` carries `accounts`, the distinct sources charged,
        and `header_copies`, the headers any delta copied during the pass —
        one for a set that pays a fee however many transactions it holds,
        none for a set that pays none, and one more a transaction where a
        nested delta that copies its header is put back into the loop — and
        `_copy_header` is called as often."""
        from test_serial_apply import close, funded, pay

        from stellar_tpu.ledger import delta as delta_module
        from stellar_tpu.ledger.manager import LedgerManager
        from stellar_tpu.main.application import Application
        from stellar_tpu.tx import testutils as T
        from stellar_tpu.tx.frame import TransactionFrame

        app = Application(clock, T.get_test_config(177), new_db=True)
        try:
            keys = [T.get_account("fc-%d" % i) for i in range(4)]
            first = funded(app, keys)
            five = lambda: (  # noqa: E731
                [pay(app, keys[0], first + n, keys[2], n) for n in (1, 2, 3)]
                + [pay(app, keys[1], first + n, keys[3], n) for n in (1, 2)]
            )
            txs, accounts, copies = {
                "two-sources-of-five": (five(), 2, 1),
                "nested-header-a-transaction": (five(), 2, 6),
                "zero-fees": (
                    [T.tx_from_ops(app, k, first + 1, [T.payment_op(keys[3], 1)], fee=0) for k in keys[:3]], 3, 0,
                ),
                "empty-set": ([], 0, 0),
            }[fee_set]
            copied = []
            real_copy = delta_module._copy_header
            real_pass = LedgerManager._process_fees_seq_nums

            def copy_header(h):
                copied.append(h)
                return real_copy(h)

            def fee_pass(self, txs, delta):
                # count the copies of the pass alone, not the apply loop's
                monkeypatch.setattr(delta_module, "_copy_header", copy_header)
                try:
                    real_pass(self, txs, delta)
                finally:
                    monkeypatch.setattr(delta_module, "_copy_header", real_copy)

            monkeypatch.setattr(LedgerManager, "_process_fees_seq_nums", fee_pass)
            if fee_set == "nested-header-a-transaction":
                real_charge = TransactionFrame.charge_fee_seq_num

                def nesting_charge(self, delta, db):
                    # what the loop did before PR 47: a delta a
                    # transaction, its header copied for the fee
                    nested = delta_module.LedgerDelta(outer=delta)
                    nested.get_header()
                    nested.rollback()
                    return real_charge(self, delta, db)

                monkeypatch.setattr(TransactionFrame, "charge_fee_seq_num", nesting_charge)
            app.tracer.clear()
            close(app, txs)
            spans = app.tracer.spans()
            assert [s.attrs for s in spans if s.name == "fees.charge"] == [
                {"txs": len(txs), "accounts": accounts, "header_copies": copies}
            ]
            assert [s.attrs for s in spans if s.name == "fees.rows"] == [{"rows": len(txs)}]
            assert len(copied) == copies
        finally:
            app.database.close()

    def test_every_span_of_a_close_carries_its_ledger(self, traced_closes):
        _traffic, closes = traced_closes
        for seq, spans in closes:
            under = [s for s in spans if s.name != "ledger.close"]
            assert under and all(s.req == seq for s in spans), {
                (s.name, s.req) for s in spans if s.req != seq
            }
            # the prewarm worker's spans included
            (close,) = [s for s in spans if s.name == "ledger.close"]
            assert {s.tid for s in spans} != {close.tid}

    def test_sampling_picks_the_same_indices_on_every_run(self, traced_closes):
        from stellar_tpu.tx.frame import TX_SAMPLE_STRIDE

        _traffic, closes = traced_closes
        picks = [
            sorted(s.attrs["index"] for s in spans if s.name == "tx.apply")
            for _seq, spans in closes
        ]
        assert picks[0] == picks[1] == picks[2]
        assert picks[0] == list(range(0, CLOSE_TXS, TX_SAMPLE_STRIDE)) == [0, 64, 128]

    def test_sampled_children_lie_in_the_transaction_in_order(self, traced_closes):
        # tx.apply's own time is what is left: the deltas' commits, the
        # result pair, the history row
        _traffic, closes = traced_closes
        for _seq, spans in closes:
            for tx in (s for s in spans if s.name == "tx.apply"):
                parts = sorted((s for s in spans if s.parent == tx.sid), key=lambda s: s.start)
                assert [s.name for s in parts] == ["tx.valid", "tx.ops"]
                assert tx.start <= parts[0].start and parts[-1].end <= tx.end
                assert parts[0].end <= parts[1].start
                assert parts[1].attrs == {"ops": 1}

    def test_span_budget_of_a_close(self, traced_closes):
        import math

        from stellar_tpu.tx.frame import TX_SAMPLE_STRIDE

        _traffic, closes = traced_closes
        budget = lambda txs: 64 + 4 * math.ceil(txs / 64)  # noqa: E731
        for _seq, spans in closes:
            new = [s for s in spans if s.name in NEW_CLOSE_SPANS]
            assert {s.name for s in spans} - NEW_CLOSE_SPANS <= {
                "ledger.close", "close.sig_flush", "close.fees", "close.apply",
                "close.commit", "close.pipeline.dispatch",
                "sig.flush_async", "sig.flush",
            } | {s.name for s in spans if s.name.startswith("invariant.")}
            assert len(new) <= budget(CLOSE_TXS)
            fixed = len([s for s in new if not s.name.startswith("tx.")])
            assert fixed <= 12
            assert [s.attrs["site"] for s in new if s.name == "accounts.warm"] == ["close", "collect"]
        # and at the widths the cells run: whole spans + 3 a sampled
        # transaction
        for txs in (1000, 5000):
            worst = 12 + 3 * math.ceil(txs / TX_SAMPLE_STRIDE)
            assert worst <= budget(txs), txs
        # a close that meets the order book adds one ``op.exchange`` a
        # conversion (tests/test_mixed_close.py counts them): at
        # ``mixed1000``'s mix under a fifth of a set — a path payment or an
        # arriving offer in 5.5 of 100 transactions each way, two
        # conversions for one path in ten
        mixed = 10 + 3 * math.ceil(1000 / TX_SAMPLE_STRIDE) + math.ceil(1000 * (0.075 * 1.1 + 0.10))
        assert mixed <= budget(1000) + 200


# what one entry in INGEST_SAMPLE_STRIDE records under its flush (PR 51)
SAMPLED_ADMISSION = ("ingest.collect", "herder.recv_transaction", "tx.check_valid")


class TestFrontDoorCounters:
    def test_submit_sync_counts_and_records_no_span_of_its_own(self, clock):
        from stellar_tpu.crypto.keys import SecretKey
        from stellar_tpu.ledger.accountframe import AccountFrame
        from stellar_tpu.main.application import Application
        from stellar_tpu.tx import testutils as T

        cfg = T.get_test_config(177)
        cfg.HTTP_PORT = 0
        app = Application.create(clock, cfg, new_db=True)
        try:
            app.start()
            assert app.ingest is not None and app.ingest.enabled
            before = app.ingest.stats()
            app.tracer.clear()
            n = 5
            root = T.root_key_for(app)
            seq = AccountFrame.load_account(root.get_public_key(), app.database).get_seq_num()
            for i in range(n):
                tx = T.tx_from_ops(
                    app, root, seq + 1 + i,
                    [T.create_account_op(SecretKey.pseudo_random_for_testing(9700 + i), 10**9)],
                )
                assert app.ingest.submit_sync(tx) == "PENDING"
            after = app.ingest.stats()
            assert after["submitted"] - before["submitted"] == n
            assert after["submit_s"] > before["submit_s"]
            names = [s.name for s in app.tracer.spans()]
            # what the edge recorded before this PR, and since PR 51 the
            # three sampled spans of the one entry in 64 (the first here)
            whole = {"ingest.flush", "sig.flush", "sig.host_verify"}
            assert set(names) <= whole | set(SAMPLED_ADMISSION), names
            assert names.count("ingest.flush") == n
            assert [names.count(k) for k in SAMPLED_ADMISSION] == [1, 1, 1]
        finally:
            app.graceful_stop()

    @pytest.mark.parametrize("edge", ["sync", "batched", "replay"])
    def test_one_entry_in_64_records_the_spans_under_its_flush(self, clock, edge):
        """128 submissions: exactly two ``ingest.collect`` /
        ``herder.recv_transaction`` / ``tx.check_valid`` triples (arrival
        indices 0 and 64), each with its ``ingest.flush`` as ancestor, the
        herder's span carrying the status — and every status what a node
        with the tracer off (no entry sampled) answers."""
        from stellar_tpu.crypto.keys import PubKeyUtils, SecretKey
        from stellar_tpu.ingest.plane import INGEST_SAMPLE_STRIDE
        from stellar_tpu.ledger.accountframe import AccountFrame
        from stellar_tpu.main.application import Application
        from stellar_tpu.tx import testutils as T

        assert INGEST_SAMPLE_STRIDE == 64
        n = 128

        def drive(instance, traced):
            cfg = T.get_test_config(instance)
            cfg.HTTP_PORT = 0
            cfg.MANUAL_CLOSE = True
            cfg.TRACE_ENABLED = traced
            cfg.INGEST_BATCH_MAX = 32
            cfg.INGEST_BATCH_DEADLINE_MS = 60_000
            app = Application.create(clock, cfg, new_db=True)
            try:
                app.start()
                PubKeyUtils.clear_verify_sig_cache()
                app.tracer.clear()
                root = T.root_key_for(app)
                seq = AccountFrame.load_account(root.get_public_key(), app.database).get_seq_num()
                txs, at = [], seq
                for i in range(n):
                    # arrival 64, a sampled one, and 65 do not follow the
                    # chain (ERROR); 66 is 63 again (DUPLICATE)
                    if i in (64, 65):
                        use = at + 7
                    elif i == 66:
                        txs.append(txs[63])
                        continue
                    else:
                        at += 1
                        use = at
                    dest = SecretKey.pseudo_random_for_testing(9900 + i)
                    txs.append(T.tx_from_ops(app, root, use, [T.create_account_op(dest, 10**9)]))
                assert app.ingest._arrivals == 0
                if edge == "sync":
                    statuses = [app.ingest.submit_sync(tx) for tx in txs]
                elif edge == "batched":
                    statuses = []
                    for tx in txs:
                        app.ingest.submit(tx, on_status=statuses.append)
                else:
                    statuses = app.ingest.submit_replay(txs)
                return statuses, app.tracer.spans(), app.ingest.stats()
            finally:
                app.graceful_stop()

        plain, none, plain_stats = drive(179, False)
        statuses, spans, stats = drive(180, True)
        assert none == []
        assert statuses == plain and len(statuses) == n
        assert statuses[63:67] == ["PENDING", "ERROR", "ERROR", "DUPLICATE"]
        assert stats["flushed"] == plain_stats["flushed"] == n
        assert stats["flushes"] == (n if edge == "sync" else n // 32)

        by = {s.sid: s for s in spans}

        def ancestors(s):
            while s.parent is not None:
                s = by[s.parent]
                yield s

        sampled = {k: [s for s in spans if s.name == k] for k in SAMPLED_ADMISSION}
        assert [len(v) for v in sampled.values()] == [2, 2, 2]
        flushes = []
        for collect, recv, valid in zip(*sampled.values()):
            assert by[collect.parent].name == "ingest.flush"
            assert recv.parent == collect.parent and valid.parent == recv.sid
            assert "ingest.flush" in [a.name for a in ancestors(valid)]
            assert collect.attrs == {"triples": 1}
            flushes.append(collect.parent)
        # the two are entries 0 and 64: of different flushes, and the
        # second is the one whose sequence number does not follow
        assert len(set(flushes)) == 2
        assert [s.attrs["status"] for s in sampled["herder.recv_transaction"]] == ["PENDING", "ERROR"]
        # selftime.py gives /trace the self time of each
        from stellar_tpu.trace import self_p50_ms

        selfs = self_p50_ms(spans)
        assert all(selfs[k] >= 0.0 for k in SAMPLED_ADMISSION)

    def test_herder_trigger_and_its_children(self, clock):
        from test_herder import create_account_tx, load_or_none, make_scp_app
        from stellar_tpu.crypto.keys import SecretKey

        app = make_scp_app(clock, instance=178)
        app.herder.bootstrap()
        dest = SecretKey.pseudo_random_for_testing(9800)
        app.herder.recv_transaction(create_account_tx(app, dest, 10**10))
        assert clock.crank_until(lambda: load_or_none(app, dest) is not None, 60)
        spans = app.tracer.spans()
        by = {s.sid: s for s in spans}
        trig = [s for s in spans if s.name == "herder.trigger"]
        assert trig
        slot = trig[0].req
        assert slot == 2
        kids = [s.name for s in spans if s.parent == trig[0].sid]
        for name in ("herder.trim_invalid", "herder.surge", "txset.validate", "scp.consensus"):
            assert name in kids, name
        # at most a dozen new spans a ledger, none per transaction
        new = [s for s in spans if s.name.startswith("herder.") and s.req == slot]
        assert len(new) <= 12
        # on a single-node network consensus and the close run inside the
        # trigger: the whole ledger is one request
        (close,) = [s for s in spans if s.name == "ledger.close"]
        assert close.req == slot
        last = max(s.sid for s in spans if s.parent == close.sid)
        inside = [s for s in spans if trig[0].sid <= s.sid <= last]
        assert len(inside) > 20
        assert all(s.req == slot for s in inside), [(s.name, s.req, s.parent) for s in inside if s.req != slot]


class TestCloseTrace:
    """A simulation ledger close must leave a Chrome-loadable trace with the
    close phases and an attribute-carrying sig-flush span."""

    def test_ledger_close_phases_traced(self, clock):
        from test_herder import create_account_tx, load_or_none, make_scp_app
        from stellar_tpu.crypto.keys import SecretKey

        app = make_scp_app(clock, instance=91)
        app.herder.bootstrap()
        dest = SecretKey.pseudo_random_for_testing(9100)
        assert (
            app.herder.recv_transaction(create_account_tx(app, dest, 10**10))
            == "PENDING"
        )
        assert clock.crank_until(lambda: load_or_none(app, dest) is not None, 60)

        names = {s.name for s in app.tracer.spans()}
        for phase in (
            "ledger.close",
            "close.sig_flush",
            "close.fees",
            "close.apply",
            "close.commit",
            # the children of the three phases that had none
            "commit.flush",
            "commit.invariants",
            "commit.buckets",
            "commit.sql",
            "fees.charge",
            "fees.rows",
            "apply.rows",
            "tx.apply",
            "tx.valid",
            "tx.ops",
            # and the herder's side of the ledger
            "herder.trigger",
            "herder.trim_invalid",
            "herder.surge",
        ):
            assert phase in names, f"missing close phase {phase}"
        # close.txset_validate (two hash compares a close, one dot away
        # from txset.validate) is gone: nothing read it
        assert "close.txset_validate" not in names
        # consensus attribution rides along
        assert "scp.consensus" in names
        assert "txset.validate" in names

        # at least one sig-flush span carries the batch/cache-hit split
        flushes = [s for s in app.tracer.spans() if s.name == "sig.flush"]
        assert flushes
        assert all(
            {"batch", "cache_hits", "misses"} <= set(s.attrs or {})
            for s in flushes
        )
        assert any(s.attrs["batch"] > 0 for s in flushes)

        # the whole thing exports as valid Chrome trace JSON
        out = json.loads(json.dumps(app.tracer.chrome_trace()))
        assert any(e["name"] == "ledger.close" for e in out["traceEvents"])

        # and /metrics carries the folded latency aggregates
        assert any(k.startswith("trace.close.") for k in app.metrics.to_json())

    def test_trace_disabled_adds_zero_spans(self, clock):
        from test_herder import create_account_tx, load_or_none, make_scp_app
        from stellar_tpu.crypto.keys import SecretKey
        from stellar_tpu.tx import testutils as T

        cfg = T.get_test_config(92)
        cfg.MANUAL_CLOSE = False
        cfg.TRACE_ENABLED = False
        from stellar_tpu.herder.herder import Herder
        from stellar_tpu.main.application import Application

        app = Application(clock, cfg, new_db=True)
        app.herder = Herder(app)
        app.herder.bootstrap()
        dest = SecretKey.pseudo_random_for_testing(9200)
        app.herder.recv_transaction(create_account_tx(app, dest, 10**10))
        assert clock.crank_until(lambda: load_or_none(app, dest) is not None, 60)
        assert app.tracer.spans() == []
        assert app.tracer.aggregates() == {}
        assert not any(k.startswith("trace.") for k in app.metrics.to_json())


class TestCommandHandlerTrace:
    def test_trace_endpoint(self, clock):
        from stellar_tpu.main.application import Application
        from stellar_tpu.tx import testutils as T

        cfg = T.get_test_config(93)
        cfg.MANUAL_CLOSE = True
        cfg.HTTP_PORT = 0
        app = Application.create(clock, cfg, new_db=True)
        try:
            app.start()
            with app.tracer.span("demo.phase", n=1):
                pass
            out = app.command_handler.execute("/trace")
            assert out["enabled"] is True
            assert any(
                e["name"] == "demo.phase" for e in out["traceEvents"]
            )
            assert "demo.phase" in out["aggregates"]
            # ?clear=1 empties the window after dumping
            app.command_handler.execute("/trace?clear=1")
            assert app.command_handler.execute("/trace")["traceEvents"] == []
        finally:
            app.graceful_stop()


class TestCollectorTrace:
    """Every full collector pass is one `gc.full` span, under whatever span
    is open, and `/info` counts them (`util/collector.py`)."""

    COUNTERS = {"full_passes", "full_pass_s", "boundary_checks", "boundary_passes", "closes_since_full"}

    def test_gc_full_under_ledger_close_and_the_info_block(self, clock, monkeypatch):
        import gc

        from stellar_tpu.main.application import Application
        from stellar_tpu.tx import testutils as T
        from stellar_tpu.util import collector

        cfg = T.get_test_config(94)
        cfg.HTTP_PORT = 0
        app = Application.create(clock, cfg, new_db=True)
        try:
            info = lambda: app.command_handler.execute("/info")["info"]["collector"]  # noqa: E731
            before = info()
            assert set(before) == self.COUNTERS
            lm = app.ledger_manager
            # a boundary at which no pass is due leaves no span
            monkeypatch.setattr(collector, "_due", lambda: False)
            T.close_ledger_on(app, lm.last_closed.header.scpValue.closeTime + 5)
            assert not [s for s in app.tracer.spans() if s.name == "gc.full"]
            assert info()["boundary_checks"] == before["boundary_checks"] + 1
            assert info()["boundary_passes"] == before["boundary_passes"]
            # one at which it is due: the pass runs inside ledger.close,
            # after the commit, and carries the close's ledger
            app.tracer.clear()
            monkeypatch.setattr(collector, "_due", lambda: True)
            T.close_ledger_on(app, lm.last_closed.header.scpValue.closeTime + 5)
            spans = app.tracer.spans()
            (close,) = [s for s in spans if s.name == "ledger.close"]
            (commit,) = [s for s in spans if s.name == "close.commit"]
            (full,) = [s for s in spans if s.name == "gc.full"]
            assert full.parent == close.sid
            assert full.req == close.req == lm.last_closed.header.ledgerSeq
            assert full.attrs["cause"] == "boundary"
            assert {"collected", "uncollectable"} <= set(full.attrs)
            assert commit.end <= full.start and full.end <= close.end
            after = info()
            assert after["full_passes"] == before["full_passes"] + 1
            assert after["boundary_checks"] == before["boundary_checks"] + 2
            assert after["boundary_passes"] == before["boundary_passes"] + 1
            assert after["closes_since_full"] == 0
            assert after["full_pass_s"] > before["full_pass_s"]
            # a pass somebody else asked for, under no span: nobody's child
            app.tracer.clear()
            gc.collect()
            (full,) = [s for s in app.tracer.spans() if s.name == "gc.full"]
            assert (full.parent, full.req, full.attrs["cause"]) == (None, None, "explicit")
            assert info()["full_passes"] == after["full_passes"] + 1
            assert "trace.gc.full" in app.metrics.to_json()
        finally:
            app.graceful_stop()
        # given back: the passes of a process without a node leave no span
        app.tracer.clear()
        gc.collect()
        assert app.tracer.spans() == []


class TestOverhead:
    """The tracer must be cheap enough to leave on (a few µs per span) and
    free when off — guards the hot path against silent regressions."""

    N = 20000

    @staticmethod
    def _per_call(fn, n):
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(n):
                fn()
            best = min(best, (time.perf_counter() - t0) / n)
        return best

    def test_disabled_span_cost_nanoscale(self):
        tr = Tracer(enabled=False)

        def one():
            with tr.span("sig.flush", batch=1, cache_hits=1, misses=0):
                pass

        # a disabled span is a dict build + one method call; "no measurable
        # overhead" with a CI-safe ceiling
        assert self._per_call(one, self.N) < 5e-6

    def test_enabled_span_cost_microscale(self):
        tr = Tracer(ring_size=1024)

        def one():
            with tr.span("sig.flush", batch=1, cache_hits=1, misses=0):
                pass

        # "a few microseconds" with headroom for loaded CI hosts
        assert self._per_call(one, self.N) < 50e-6

    def test_sig_cache_loop_on_vs_off(self):
        """The instrumented CachingSigBackend path, exactly as the node
        runs it, around a tight all-cache-hit loop."""
        from stellar_tpu.crypto.keys import SecretKey
        from stellar_tpu.crypto.sigbackend import CachingSigBackend, CpuSigBackend
        from stellar_tpu.crypto.sigcache import VerifySigCache

        sk = SecretKey.pseudo_random_for_testing(31337)
        msg = b"overhead probe"
        items = [(sk.public_raw, msg, sk.sign(msg))]

        def run(tracer, n=3000):
            backend = CachingSigBackend(
                CpuSigBackend(), VerifySigCache(), tracer=tracer
            )
            backend.verify_batch(items)  # warm: the loop below is pure cache
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(n):
                    assert backend.verify_batch(items) == [True]
                best = min(best, (time.perf_counter() - t0) / n)
            return best

        t_off = run(Tracer(enabled=False))
        t_on = run(Tracer(ring_size=4096))
        # tracing on may cost a few µs per flush, never tens
        assert t_on - t_off < 50e-6, (t_on, t_off)
