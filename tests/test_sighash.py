"""Differential suite for native/sighash.c — the C host stage (strict
gate + batch SHA-512(R‖A‖M) mod L + packed transposed staging) must be
bit-exact with hashlib + the Python gate (ops/ref25519) over random
lengths, padding boundaries, >1 MiB messages and hostile inputs; the
thread fanout must be deterministic; and the GIL must actually be
released (the property the whole staging pipeline rests on)."""

import hashlib
import random
import threading
import time

import numpy as np
import pytest

from stellar_tpu import native
from stellar_tpu.crypto import SecretKey
from stellar_tpu.ops import ref25519 as ref

sighash = native.load_sighash()
pytestmark = pytest.mark.skipif(
    sighash is None, reason="no C toolchain for the native host stage"
)

BLACKLIST = b"".join(ref.small_order_blacklist())
L = ref.L


def stage_all(items, bucket=None, threads=0):
    n = len(items)
    bucket = bucket or n
    packed = np.full((128, bucket), 0xAA, dtype=np.uint8)  # catch non-writes
    ok = np.zeros(bucket, dtype=np.uint8)
    rejects = sighash.stage(items, 0, n, packed, ok, BLACKLIST, threads)
    return packed, ok[:n].astype(bool), rejects


def expected_h(pk, msg, sig):
    h = (
        int.from_bytes(hashlib.sha512(sig[:32] + pk + msg).digest(), "little")
        % L
    )
    return np.frombuffer(h.to_bytes(32, "little"), dtype=np.uint8)


class TestReduction:
    def test_reduce512_edges_and_fuzz(self):
        rng = random.Random(3)
        vals = [
            0, 1, L - 1, L, L + 1, 2 * L, 8 * L + 5,
            2**252, 2**252 - 1, 2**253 - 1, 2**256 - 1,
            2**511, 2**512 - 1, (L << 255) + 12345,
        ] + [rng.getrandbits(512) for _ in range(2000)]
        for v in vals:
            got = int.from_bytes(
                sighash._reduce512(v.to_bytes(64, "little")), "little"
            )
            assert got == v % L, v


class TestSha512:
    def test_block_boundaries_vs_hashlib(self):
        """Every message length around the padding cliffs: the ≤111-byte
        single-block fast path (the fixed 96-byte preimage class lives
        there), the 112..127 two-block pad, and multi-block streams."""
        rng = random.Random(7)
        r = bytes(rng.getrandbits(8) for _ in range(32))
        a = bytes(rng.getrandbits(8) for _ in range(32))
        for mlen in list(range(0, 200)) + [255, 256, 257, 4096]:
            m = bytes(rng.getrandbits(8) for _ in range(mlen))
            assert (
                sighash._sha512_rax(r, a, m)
                == hashlib.sha512(r + a + m).digest()
            ), mlen

    def test_large_message(self):
        m = bytes(range(256)) * 4200  # > 1 MiB
        r, a = b"\x01" * 32, b"\x02" * 32
        assert (
            sighash._sha512_rax(r, a, m) == hashlib.sha512(r + a + m).digest()
        )


class TestStageDifferential:
    def _items(self, rng, n=96):
        items = []
        for i in range(n):
            sk = SecretKey.pseudo_random_for_testing(i)
            mlen = rng.choice([0, 1, 31, 32, 33, 47, 48, 64, 111, 200])
            msg = bytes(rng.getrandbits(8) for _ in range(mlen))
            sig = bytearray(sk.sign(msg))
            pk = bytearray(sk.public_raw)
            if i % 3 == 1:
                sig[rng.randrange(64)] ^= 1 << rng.randrange(8)
            if i % 7 == 3:
                pk[rng.randrange(32)] ^= 1 << rng.randrange(8)
            items.append((bytes(pk), msg, bytes(sig)))
        # hostile classes: small-order R/A, s >= L, non-canonical A,
        # malformed lengths
        sk = SecretKey.pseudo_random_for_testing(999)
        good = sk.sign(b"x")
        for e in ref.small_order_blacklist():
            items.append((e, b"x", good))
            items.append((sk.public_raw, b"x", e + good[32:]))
        bad_s = (int.from_bytes(good[32:], "little") + L).to_bytes(
            32, "little"
        )
        items.append((sk.public_raw, b"x", good[:32] + bad_s))
        items.append(((2**255 - 5).to_bytes(32, "little"), b"x", good))
        items.append((sk.public_raw[:31], b"x", good))
        items.append((sk.public_raw, b"x", good + b"\x00"))
        items.append((sk.public_raw, b"", sk.sign(b"")))  # empty message
        return items

    def test_gate_and_hash_match_python(self):
        rng = random.Random(11)
        items = self._items(rng)
        packed, ok, rejects = stage_all(items, bucket=len(items) + 5)
        want_ok = [
            len(p) == 32 and len(s) == 64 and ref.strict_input_ok(p, s)
            for p, _, s in items
        ]
        assert ok.tolist() == want_ok
        assert rejects == len(items) - sum(want_ok)
        for j, (p, m, s) in enumerate(items):
            if not want_ok[j]:
                continue
            assert bytes(packed[0:32, j]) == p
            assert bytes(packed[32:64, j]) == s[:32]
            assert bytes(packed[64:96, j]) == s[32:]
            assert (packed[96:128, j] == expected_h(p, m, s)).all(), j
        # bucket padding columns are zeroed
        assert (packed[:, len(items):] == 0).all()

    def test_gate_rejected_lane_columns_are_inert(self):
        """Rejected lanes skip the hash: the h column must be zero (the
        drain-side mask makes lane content irrelevant, but an inert lane
        keeps padded-bucket behavior deterministic)."""
        sk = SecretKey.pseudo_random_for_testing(5)
        good = sk.sign(b"x")
        bad_s = (int.from_bytes(good[32:], "little") + L).to_bytes(
            32, "little"
        )
        packed, ok, rejects = stage_all(
            [(sk.public_raw, b"x", good[:32] + bad_s)]
        )
        assert not ok[0] and rejects == 1
        assert (packed[96:128, 0] == 0).all()

    def test_large_message_through_stage(self):
        sk = SecretKey.pseudo_random_for_testing(17)
        msg = bytes(range(256)) * 4500  # > 1 MiB
        sig = sk.sign(msg)
        packed, ok, _ = stage_all([(sk.public_raw, msg, sig)])
        assert ok[0]
        assert (packed[96:128, 0] == expected_h(sk.public_raw, msg, sig)).all()

    def test_fast_path_96_byte_preimage(self):
        """The dominant verify class: a 32-byte contents hash -> a fixed
        96-byte single-block preimage."""
        for i in range(32):
            sk = SecretKey.pseudo_random_for_testing(1000 + i)
            msg = hashlib.sha256(b"contents %d" % i).digest()
            sig = sk.sign(msg)
            packed, ok, _ = stage_all([(sk.public_raw, msg, sig)])
            assert ok[0]
            assert (
                packed[96:128, 0] == expected_h(sk.public_raw, msg, sig)
            ).all()

    def test_tuple_slots_and_sequence_window(self):
        """stage() uses the LAST three tuple slots ((idx, pk, msg, sig)
        verifier tuples and bare triples both work) and honors
        [start, start+count) windows."""
        sk = SecretKey.pseudo_random_for_testing(2)
        msg = b"windowed"
        sig = sk.sign(msg)
        items = [
            ("pad", b"", b"", b""),
            (7, sk.public_raw, msg, sig),
            (sk.public_raw, msg, sig),
        ]
        packed = np.zeros((128, 2), np.uint8)
        ok = np.zeros(2, np.uint8)
        rejects = sighash.stage(items, 1, 2, packed, ok, BLACKLIST)
        assert rejects == 0 and ok.all()
        assert (packed[:, 0] == packed[:, 1]).all()

    def test_argument_validation(self):
        packed = np.zeros((128, 2), np.uint8)
        ok = np.zeros(2, np.uint8)
        with pytest.raises(ValueError):  # count beyond items
            sighash.stage([], 0, 3, packed, ok, BLACKLIST)
        with pytest.raises(ValueError):  # out too small
            sighash.stage(
                [(b"a" * 32, b"", b"b" * 64)] * 3, 0, 3, packed, ok,
                BLACKLIST,
            )
        with pytest.raises(TypeError):  # non-bytes item slot
            sighash.stage([(b"a" * 32, 17, b"b" * 64)], 0, 1, packed, ok,
                          BLACKLIST)
        with pytest.raises(TypeError):  # mutable buffers are refused:
            # pointers are borrowed across the GIL-released pass, and a
            # concurrent resize of a bytearray would dangle them
            sighash.stage([(b"a" * 32, bytearray(b"m"), b"b" * 64)], 0, 1,
                          packed, ok, BLACKLIST)
        with pytest.raises(ValueError):  # ragged blacklist
            sighash.stage([(b"a" * 32, b"", b"b" * 64)], 0, 1, packed, ok,
                          b"xyz")


class TestThreading:
    def _bulk(self, n):
        items = []
        for i in range(n):
            sk = SecretKey.pseudo_random_for_testing(i % 512)
            msg = b"bulk %d" % i
            sig = sk.sign(msg) if i % 5 else b"\x00" * 64
            items.append((sk.public_raw, msg, sig))
        return items

    def test_fanout_determinism(self):
        """Inline (threads=1) and pooled (threads=0, above the 2048-item
        fanout threshold) runs must produce identical buffers."""
        items = self._bulk(5000)
        p1, ok1, r1 = stage_all(items, bucket=8192, threads=1)
        p2, ok2, r2 = stage_all(items, bucket=8192, threads=0)
        assert r1 == r2
        assert (ok1 == ok2).all()
        assert (p1 == p2).all()

    def test_gil_released_during_stage(self):
        """While one thread runs the C stage, a pure-Python thread must
        keep making progress — a C call that held the GIL would block it
        completely (no preemption inside a C call)."""
        items = self._bulk(4096)
        packed = np.zeros((128, 4096), np.uint8)
        ok = np.zeros(4096, np.uint8)
        done = threading.Event()

        def churn():
            # keep the C stage busy long enough to observe overlap
            for _ in range(60):
                sighash.stage(items, 0, 4096, packed, ok, BLACKLIST, 1)
            done.set()

        t = threading.Thread(target=churn, daemon=True)
        count = 0
        t.start()
        while not done.is_set():
            count += 1
        t.join(60)
        assert done.is_set(), "stage thread never finished"
        # with the GIL held for each full stage() call the main loop
        # would only run between calls; require real concurrent progress
        assert count > 1000, count


class TestPipelineOverlap:
    def test_c_stage_overlaps_fake_device_dispatch(self):
        """The pipeline property the GIL-releasing C stage exists for:
        with streams=1, chunk k+1's host stage (on the stager thread)
        runs while chunk k's device result is still in flight — i.e.
        BEFORE the main thread has drained it.  A serial implementation
        (stage, dispatch, drain, stage, ...) fails this ordering."""
        from stellar_tpu.ops.verifier import BatchVerifier

        bv = BatchVerifier(max_batch=64, streams=1)
        assert bv._sighash is not None
        events = []
        ev_lock = threading.Lock()

        def mark(name):
            with ev_lock:
                events.append((name, time.monotonic()))

        real_stage = bv._stage_chunk

        def traced_stage(items, start, n):
            mark("stage_start:%d" % start)
            staged = real_stage(items, start, n)
            mark("stage_end:%d" % start)
            return staged

        class SlowResult:
            """Fake in-flight device result: materializing it (what
            drain_one's np.asarray does) blocks like a real device."""

            def __init__(self, n):
                self.n = n

            def __array__(self, dtype=None, copy=None):
                mark("drain_sleep_start")
                time.sleep(0.25)
                mark("drain_sleep_end")
                arr = np.ones(self.n, dtype=bool)
                return arr if dtype is None else arr.astype(dtype)

        real_dispatch_counter = []

        def fake_dispatch(staged):
            real_dispatch_counter.append(staged.n)
            return SlowResult(staged.packed.shape[1])

        bv._stage_chunk = traced_stage
        bv._dispatch_staged = fake_dispatch
        items = []
        for i in range(64 * 3):  # 3 chunks
            sk = SecretKey.pseudo_random_for_testing(i)
            msg = b"overlap %d" % i
            items.append((sk.public_raw, msg, sk.sign(msg)))
        out = bv.verify(items)
        assert all(out)
        assert real_dispatch_counter == [64, 64, 64]
        times = {}
        for name, t in events:
            times.setdefault(name, t)  # first occurrence
        # chunk 1 (start=64) staged on the stager thread before chunk 0's
        # result was drained on the main thread
        first_drain_end = times["drain_sleep_end"]
        assert times["stage_start:64"] < first_drain_end, events


class TestVerifierPaths:
    def test_native_and_python_stages_agree_end_to_end(self):
        """BatchVerifier(native_hash=True/False) must return identical
        verdicts over a mixed valid/corrupt/hostile batch (the bench
        host-stage A/B's correctness precondition)."""
        from stellar_tpu.ops.verifier import BatchVerifier

        rng = random.Random(23)
        items = []
        for i in range(70):
            sk = SecretKey.pseudo_random_for_testing(300 + i)
            msg = bytes(rng.getrandbits(8) for _ in range(rng.randrange(80)))
            sig = bytearray(sk.sign(msg))
            if i % 3 == 0:
                sig[rng.randrange(64)] ^= 1 << rng.randrange(8)
            items.append((sk.public_raw, msg, bytes(sig)))
        sk = SecretKey.pseudo_random_for_testing(999)
        good = sk.sign(b"x")
        bad_s = (int.from_bytes(good[32:], "little") + L).to_bytes(
            32, "little"
        )
        items.append((sk.public_raw, b"x", good[:32] + bad_s))
        items.append((next(iter(ref.small_order_blacklist())), b"x", good))
        items.append((sk.public_raw[:31], b"x", good))

        nat = BatchVerifier(max_batch=64, min_device_batch=16,
                            native_hash=True)
        pyv = BatchVerifier(max_batch=64, min_device_batch=16,
                            native_hash=False)
        assert nat._sighash is not None and pyv._sighash is None
        pyv._programs.kernel = nat._programs.kernel  # share the compiled kernel
        got_nat = nat.verify(items)
        got_py = pyv.verify(items)
        assert got_nat == got_py
        assert nat.n_gate_rejects == pyv.n_gate_rejects == 3
        from stellar_tpu.crypto import sodium

        want = [sodium.verify_detached(s, m, p) for p, m, s in items]
        assert got_nat == want

    def test_native_hash_resolves_as_passed(self):
        from stellar_tpu.ops.verifier import BatchVerifier

        off = BatchVerifier(max_batch=16, native_hash=False)
        assert off._sighash is None and off.stats()["native_host_stage"] is False
        on = BatchVerifier(max_batch=16)
        assert on._sighash is not None and on.stats()["native_host_stage"] is True

    def test_staging_pool_reuses_buffers(self):
        from stellar_tpu.ops.verifier import _StagingPool

        pool = _StagingPool()
        bufs = pool.acquire(64)
        assert bufs[0].shape == (128, 64) and bufs[1].shape == (64,)
        pool.release(bufs)
        again = pool.acquire(64)
        assert again[0] is bufs[0]
        assert pool.acquire(64)[0] is not bufs[0]  # pool drained: fresh
        pool.release(None)  # no-op


class TestSodiumVerifyPool:
    """The pure-CPU fallback leg (round 9): sodium_verify fans libsodium's
    crypto_sign_verify_detached over the worker pool with the GIL
    released.  Verdicts must be byte-identical to the serial
    sodium.verify_detached loop — valid, corrupted, and wrong-length
    items — across the inline and pooled paths."""

    def _batch(self, n=300, seed=41):
        rng = random.Random(seed)
        items = []
        for i in range(n):
            sk = SecretKey.pseudo_random_for_testing(7000 + i)
            msg = bytes(rng.getrandbits(8) for _ in range(rng.randrange(120)))
            sig = bytearray(sk.sign(msg))
            pk = sk.public_raw
            r = i % 5
            if r == 1:
                sig[rng.randrange(64)] ^= 1 << rng.randrange(8)  # corrupt
            elif r == 2:
                msg = msg + b"!"  # verify different message
            elif r == 3:
                sig = sig[:40]  # wrong sig length -> False precheck
            elif r == 4:
                pk = pk[:31]  # wrong pk length -> False precheck
            items.append((pk, bytes(msg), bytes(sig)))
        return items

    def _run(self, items, threads=0):
        from stellar_tpu.crypto import sodium

        ok = bytearray(len(items))
        sighash.sodium_verify(sodium.verify_fn_addr(), items, ok, threads)
        return [bool(b) for b in ok]

    def test_differential_vs_serial_loop(self):
        from stellar_tpu.crypto import sodium

        items = self._batch()
        want = [sodium.verify_detached(s, m, p) for p, m, s in items]
        assert self._run(items, threads=0) == want  # pooled (n >= 64)
        assert self._run(items, threads=1) == want  # forced inline
        assert any(want) and not all(want)

    def test_sigbackend_native_leg_matches_python_pool(self):
        """crypto/sigbackend routes big batches through the native pool;
        the returned verdicts must equal the serial-loop contract (the
        cpu_count()==1 / small-batch path stays the untouched loop)."""
        from stellar_tpu.crypto import sigbackend, sodium

        items = self._batch(n=280, seed=42)
        got = sigbackend._sodium_verify_native(items)
        assert got is not None
        assert got == [
            sodium.verify_detached(s, m, p) for p, m, s in items
        ]
        assert sigbackend._sodium_verify_loop(items) == got

    def test_non_bytes_item_falls_back(self):
        """A non-bytes buffer in the batch makes the native leg decline
        (return None) so the Python loop handles it."""
        from stellar_tpu.crypto import sigbackend

        items = self._batch(n=257, seed=43)
        pk, msg, sig = items[100]
        items[100] = (pk, bytearray(msg), sig)  # not bytes
        assert sigbackend._sodium_verify_native(items) is None

    def test_argument_validation(self):
        from stellar_tpu.crypto import sodium

        items = self._batch(n=4, seed=44)
        with pytest.raises(ValueError):  # null fn pointer
            sighash.sodium_verify(0, items, bytearray(4))
        with pytest.raises(ValueError):  # ok buffer too small
            sighash.sodium_verify(
                sodium.verify_fn_addr(), items, bytearray(3)
            )
        with pytest.raises(TypeError):  # malformed item tuple
            sighash.sodium_verify(
                sodium.verify_fn_addr(), [(b"a", b"b")], bytearray(1)
            )
