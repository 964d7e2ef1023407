"""Util runtime tests (reference style: util/TimerTests.cpp — virtual-time
scheduling determinism)."""

import os
import tempfile

import pytest

import stellar_tpu.xdr as X
from stellar_tpu.util import (
    REAL_TIME,
    VIRTUAL_TIME,
    MetricsRegistry,
    TmpDirManager,
    VirtualClock,
    VirtualTimer,
    XDRInputFileStream,
    XDROutputFileStream,
)


class TestVirtualClock:
    def test_virtual_time_advances_to_deadlines(self):
        """TimerTests.cpp:86-143 'virtual event dispatch order and times'
        (deadline-ordered dispatch; the exact-time half is below)."""
        clock = VirtualClock(VIRTUAL_TIME)
        fired = []
        for delay in (5.0, 1.0, 3.0):
            t = VirtualTimer(clock)
            t.expires_from_now(delay)
            t.async_wait(lambda d=delay: fired.append(d))
        while clock.crank():
            pass
        assert fired == [1.0, 3.0, 5.0]  # deadline order, not arming order
        assert clock.now() == 5.0
        clock.shutdown()

    def test_posted_work_runs_before_time_jumps(self):
        clock = VirtualClock(VIRTUAL_TIME)
        order = []
        t = VirtualTimer(clock)
        t.expires_from_now(10)
        t.async_wait(lambda: order.append("timer"))
        clock.post(lambda: order.append("posted"))
        while clock.crank():
            pass
        assert order == ["posted", "timer"]
        clock.shutdown()

    def test_cancel_fires_on_cancel_not_trigger(self):
        """TimerTests.cpp:209-257 'timer cancels'."""
        clock = VirtualClock(VIRTUAL_TIME)
        events = []
        t = VirtualTimer(clock)
        t.expires_from_now(5)
        t.async_wait(lambda: events.append("fired"), lambda: events.append("cancelled"))
        t.cancel()
        while clock.crank():
            pass
        assert events == ["cancelled"]
        assert clock.now() == 0.0  # cancelled timer must not advance time
        clock.shutdown()

    def test_dispatch_times_are_exact(self):
        """TimerTests.cpp:86-143, exact-time half: each handler observes
        now() == its own deadline — the clock advances to, never past."""
        clock = VirtualClock(VIRTUAL_TIME)
        seen = []
        for ms in (0.001, 0.020, 0.021, 0.200):
            t = VirtualTimer(clock)
            t.expires_from_now(ms)
            t.async_wait(lambda m=ms: seen.append((m, clock.now())))
        while clock.crank():
            pass
        assert seen == [(m, m) for m in (0.001, 0.020, 0.021, 0.200)]
        clock.shutdown()

    def test_shared_clock_two_services_advance_when_both_idle(self):
        """TimerTests.cpp:145-207 'shared virtual time advances only when
        all apps idle': two services on ONE clock; time only jumps to the
        next deadline across both, so their timers interleave on the
        shared timeline instead of one service racing ahead."""
        clock = VirtualClock(VIRTUAL_TIME)
        log = []
        def arm(tag, delay, n):
            if n == 0:
                return
            t = VirtualTimer(clock)
            t.expires_from_now(delay)
            t.async_wait(lambda: (log.append((tag, clock.now())),
                                  arm(tag, delay, n - 1)))
        arm("a", 0.3, 3)   # a fires at .3 .6 .9
        arm("b", 0.2, 4)   # b fires at .2 .4 .6 .8
        while clock.crank():
            pass
        assert log == sorted(log, key=lambda e: e[1])
        assert [t for t, _ in log] == ["b", "a", "b", "a", "b", "b", "a"]
        clock.shutdown()

    def test_timer_rearm(self):
        clock = VirtualClock(VIRTUAL_TIME)
        hits = []

        def rearm():
            hits.append(clock.now())
            if len(hits) < 3:
                t.expires_from_now(2)
                t.async_wait(rearm)

        t = VirtualTimer(clock)
        t.expires_from_now(2)
        t.async_wait(rearm)
        while clock.crank():
            pass
        assert hits == [2.0, 4.0, 6.0]
        clock.shutdown()

    def test_worker_post_back(self):
        clock = VirtualClock(REAL_TIME)
        done = []
        clock.submit_work(lambda: 21 * 2, lambda res: done.append(res))
        deadline = 5.0
        import time

        start = time.monotonic()
        while not done and time.monotonic() - start < deadline:
            clock.crank(block=True)
        assert done == [42]
        clock.shutdown()

    def test_worker_exception_delivered(self):
        clock = VirtualClock(REAL_TIME)
        done = []

        def boom():
            raise ValueError("kaboom")

        clock.submit_work(boom, lambda res: done.append(res))
        import time

        start = time.monotonic()
        while not done and time.monotonic() - start < 5:
            clock.crank(block=True)
        assert isinstance(done[0], ValueError)
        clock.shutdown()

    def test_crank_until_virtual(self):
        clock = VirtualClock(VIRTUAL_TIME)
        state = []
        t = VirtualTimer(clock)
        t.expires_from_now(30)
        t.async_wait(lambda: state.append(1))
        assert clock.crank_until(lambda: bool(state), timeout=60)
        assert clock.now() == 30.0
        clock.shutdown()

    def test_crank_until_gives_up(self):
        clock = VirtualClock(VIRTUAL_TIME)
        assert not clock.crank_until(lambda: False, timeout=5)
        clock.shutdown()


class TestMetrics:
    def test_meter_counts(self):
        reg = MetricsRegistry()
        m = reg.new_meter(("scp", "envelope", "emit"), "envelope")
        m.mark()
        m.mark(3)
        assert m.count == 4
        assert reg.new_meter(("scp", "envelope", "emit")) is m

    def test_timer_percentiles(self):
        reg = MetricsRegistry()
        t = reg.new_timer(("ledger", "transaction", "apply"))
        for ms in range(1, 101):
            t.update(ms / 1000.0)
        j = t.to_json()
        assert j["count"] == 100
        assert 40 <= j["median"] <= 60
        assert j["99%"] >= 95

    def test_registry_json(self):
        reg = MetricsRegistry()
        reg.new_counter(("a", "b", "c")).inc(5)
        j = reg.to_json()
        assert j["a.b.c"]["count"] == 5


class TestXdrStream:
    def test_roundtrip_with_record_marks(self, tmp_path):
        path = str(tmp_path / "stream.xdr")
        entries = [
            X.BucketEntry(
                X.BucketEntryType.DEADENTRY,
                X.LedgerKey(
                    X.LedgerEntryType.ACCOUNT,
                    X.LedgerKeyAccount(X.PublicKey.from_ed25519(bytes([i]) * 32)),
                ),
            )
            for i in range(5)
        ]
        with XDROutputFileStream(path) as out:
            for e in entries:
                out.write_one(e)
        with open(path, "rb") as f:
            first = f.read(4)
        assert first[0] & 0x80  # record mark continuation bit
        with XDRInputFileStream(path) as inp:
            back = list(inp.read_all(X.BucketEntry))
        assert back == entries

    def test_hasher_sees_frames(self, tmp_path):
        from stellar_tpu.crypto import SHA256

        path = str(tmp_path / "s.xdr")
        h = SHA256()
        with XDROutputFileStream(path, hasher=h) as out:
            out.write_one(X.SCPBallot(1, b"x"))
        digest = h.finish()
        with open(path, "rb") as f:
            data = f.read()
        from stellar_tpu.crypto import sha256

        assert digest == sha256(data)


class TestTmpDir:
    def test_lifecycle(self, tmp_path):
        mgr = TmpDirManager(str(tmp_path / "tmp"))
        d = mgr.tmp_dir("bucket")
        assert os.path.isdir(d.get_name())
        mgr.forget(d)
        assert not os.path.exists(d.get_name())

    def test_orphans_reaped_at_boot_live_dirs_guarded(self, tmp_path):
        """ISSUE r18 satellite: a killed process's publish-*/catchup-*
        staging dirs are reaped (and counted) at the next boot, but a
        runtime re-sweep never touches dirs this manager handed out."""
        root = str(tmp_path / "tmp")
        os.makedirs(os.path.join(root, "publish-7-dead"))
        os.makedirs(os.path.join(root, "catchup-beef"))
        mgr = TmpDirManager(root)
        assert mgr.reaped_at_boot == 2
        assert os.listdir(root) == []
        live = mgr.tmp_dir("publish-8")
        os.makedirs(os.path.join(root, "publish-9-orphan"))
        assert mgr.reap_orphans() == 1  # the orphan, never the live dir
        assert os.path.isdir(live.get_name())
        assert not os.path.exists(os.path.join(root, "publish-9-orphan"))


class TestConfigStreamsKnob:
    def test_sig_verify_streams_validation(self):
        # the TpuSigBackend plumbing assertion lives in the jax-guarded
        # tests/test_ed25519_tpu.py TestMultiStream
        import pytest

        from stellar_tpu.main.config import Config

        cfg = Config()
        assert cfg.SIG_VERIFY_STREAMS >= 1
        cfg.validate()
        cfg.SIG_VERIFY_STREAMS = 0
        with pytest.raises(ValueError, match="SIG_VERIFY_STREAMS"):
            cfg.validate()
        cfg.SIG_VERIFY_STREAMS = "2"
        with pytest.raises(ValueError, match="SIG_VERIFY_STREAMS"):
            cfg.validate()

