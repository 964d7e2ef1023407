"""Differential tests: JAX batched ed25519 verify vs libsodium + pure-Python
oracle (the bit-exactness requirement from BASELINE.md).

Layers:
1. field arithmetic vs Python ints (exhaustive op coverage, edge values)
2. point ops vs the ref25519 oracle (which itself matches libsodium)
3. BatchVerifier end-to-end vs libsodium: RFC 8032 vectors, random valid,
   random mutated, and adversarial inputs (small-order points, non-canonical
   scalars/field elements) — the libsodium strict-gate cases.

Runs on CPU (conftest forces jax_platforms=cpu); the kernel compile (~70s)
is amortized by the persistent compilation cache in stellar_tpu/ops.
"""

import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from stellar_tpu.crypto import SecretKey, sodium  # noqa: E402
from stellar_tpu.ops import fe, ref25519 as ref  # noqa: E402
from stellar_tpu.ops import ed25519 as ed  # noqa: E402
from stellar_tpu.ops.verifier import BatchVerifier  # noqa: E402

pytestmark = pytest.mark.tpu_kernel


def _to_fe(vals):
    return jnp.asarray(np.stack([fe.int_to_limbs(v) for v in vals], axis=1))


def _from_fe(arr, i):
    return fe.limbs_to_int(np.asarray(arr)[:, i])


class TestFieldArithmetic:
    P = ref.P

    @pytest.fixture(scope="class")
    def vals(self):
        rng = random.Random(5)
        return (
            [rng.randrange(self.P) for _ in range(6)]
            + [0, 1, 19, self.P - 1, 2**255 - 20, 2**255 - 19]
        )

    def test_mul_matches_python(self, vals):
        a = _to_fe(vals)
        b = _to_fe(list(reversed(vals)))
        got = jax.jit(fe.mul)(a, b)
        for i, (x, y) in enumerate(zip(vals, reversed(vals))):
            assert _from_fe(got, i) % self.P == x * y % self.P

    def test_sub_neg_matches_python(self, vals):
        a = _to_fe(vals)
        b = _to_fe(list(reversed(vals)))
        got = jax.jit(fe.sub)(a, b)
        for i, (x, y) in enumerate(zip(vals, reversed(vals))):
            assert _from_fe(got, i) % self.P == (x - y) % self.P
        gotn = jax.jit(fe.neg)(a)
        for i, x in enumerate(vals):
            assert _from_fe(gotn, i) % self.P == (-x) % self.P

    def test_inv_and_p58(self, vals):
        nz = [v if v else 7 for v in vals]
        a = _to_fe(nz)
        got = jax.jit(fe.inv)(a)
        for i, x in enumerate(nz):
            assert _from_fe(got, i) % self.P == pow(x, self.P - 2, self.P)
        got = jax.jit(fe.pow_p58)(a)
        for i, x in enumerate(nz):
            assert _from_fe(got, i) % self.P == pow(x, (self.P - 5) // 8, self.P)

    def test_inv_batch_tree_matches_inv(self):
        # width 512 forces two tree levels (512 -> 256 -> 128); a zero lane
        # must not poison the others (its own slot is unspecified)
        rng = random.Random(17)
        vals = [rng.randrange(1, self.P) for _ in range(512)]
        zero_lane = 137
        vals[zero_lane] = 0
        a = _to_fe(vals)
        got = jax.jit(lambda x: fe.inv_batch(x, min_width=128))(a)
        for i, x in enumerate(vals):
            if i == zero_lane:
                continue
            assert _from_fe(got, i) % self.P == pow(x, self.P - 2, self.P)

    def test_inv_batch_small_and_odd_widths_fall_back(self):
        rng = random.Random(19)
        for width in (5, 16):
            vals = [rng.randrange(1, self.P) for _ in range(width)]
            got = jax.jit(fe.inv_batch)(_to_fe(vals))
            for i, x in enumerate(vals):
                assert _from_fe(got, i) % self.P == pow(x, self.P - 2, self.P)

    def test_canonical_edges(self):
        edge = [0, 1, self.P - 1, self.P, self.P + 5, 2**255 - 1]
        got = jax.jit(fe.canonical)(_to_fe(edge))
        for i, v in enumerate(edge):
            assert _from_fe(got, i) == v % self.P

    def test_byte_roundtrip(self):
        rng = random.Random(9)
        vals = [rng.randrange(self.P) for _ in range(4)]
        bts = np.zeros((32, 4), dtype=np.int32)
        for i, v in enumerate(vals):
            bts[:, i] = np.frombuffer(v.to_bytes(32, "little"), dtype=np.uint8)
        lim = fe.limbs_from_bytes(jnp.asarray(bts))
        assert [_from_fe(lim, i) for i in range(4)] == vals
        back = np.asarray(fe.bytes_from_limbs(jax.jit(fe.canonical)(lim)))
        assert np.array_equal(back, bts)


class TestPointOps:
    @pytest.fixture(scope="class")
    def points(self):
        rng = random.Random(11)
        pts = []
        while len(pts) < 4:
            y = rng.randrange(ref.P)
            pt = ref.decompress(int.to_bytes(y | (rng.randrange(2) << 255), 32, "little"))
            if pt is not None:
                pts.append(pt)
        return pts

    @staticmethod
    def _dev(pts):
        return tuple(
            jnp.asarray(
                np.stack([fe.int_to_limbs(p[c] % ref.P) for p in pts], axis=1)
            )
            for c in range(4)
        )

    @staticmethod
    def _host(P4, i):
        return tuple(_from_fe(P4[c], i) % ref.P for c in range(4))

    def test_add_double_vs_oracle(self, points):
        d = self._dev(points)
        got = jax.jit(ed.point_add)(d, d)
        got2 = jax.jit(ed.point_double)(d)
        for i, p in enumerate(points):
            want = ref.point_add(p, p)
            assert ref.point_equal(self._host(got, i), want)
            assert ref.point_equal(self._host(got2, i), want)

    def test_identity_neutral(self, points):
        d = self._dev(points)
        ident = ed.point_identity(len(points))
        got = jax.jit(ed.point_add)(d, ident)
        for i, p in enumerate(points):
            assert ref.point_equal(self._host(got, i), p)

    def test_compress_decompress_roundtrip(self, points):
        d = self._dev(points)
        enc = np.asarray(jax.jit(ed.compress)(d))
        for i, p in enumerate(points):
            assert bytes(enc[:, i].astype(np.uint8)) == ref.compress(p)


class TestBatchVerifier:
    @pytest.fixture(scope="class")
    def bv(self):
        return BatchVerifier(max_batch=64, min_device_batch=16)

    def test_rfc8032_vectors(self, bv):
        """RFC 8032 §7.1 TEST 1-3."""
        cases = [
            (
                "9d61b19deffd5a60ba844af492ec2cc44449c5697b326919703bac031cae7f60",
                b"",
            ),
            (
                "4ccd089b28ff96da9db6c346ec114e0f5b8a319f35aba624da8cf6ed4fb8a6fb",
                b"\x72",
            ),
            (
                "c5aa8df43f9f837bedb7442f31dcb7b166d38535076f094b85ce3a2e0b4458f7",
                b"\xaf\x82",
            ),
        ]
        items = []
        for seed_hex, msg in cases:
            sk = SecretKey.from_seed(bytes.fromhex(seed_hex))
            items.append((sk.public_raw, msg, sk.sign(msg)))
        assert bv.verify(items) == [True, True, True]

    def test_differential_random_mutations(self, bv):
        rng = random.Random(1234)
        items = []
        for i in range(48):
            sk = SecretKey.pseudo_random_for_testing(i)
            msg = bytes([rng.randrange(256) for _ in range(rng.randrange(0, 100))])
            sig = bytearray(sk.sign(msg))
            if i % 2:
                sig[rng.randrange(64)] ^= 1 << rng.randrange(8)
            items.append((sk.public_raw, msg, bytes(sig)))
        want = [sodium.verify_detached(s, m, p) for p, m, s in items]
        assert bv.verify(items) == want

    def test_adversarial_inputs_match_libsodium(self, bv):
        sk = SecretKey.pseudo_random_for_testing(0)
        msg = b"m"
        sig = sk.sign(msg)
        adv = []
        for e in ref.small_order_blacklist():
            adv.append((e, msg, sig))  # small-order pk
            adv.append((sk.public_raw, msg, e + sig[32:]))  # small-order R
        bad_s = (int.from_bytes(sig[32:], "little") + ref.L).to_bytes(32, "little")
        adv.append((sk.public_raw, msg, sig[:32] + bad_s))  # s >= L
        adv.append(((2**255 - 5).to_bytes(32, "little"), msg, sig))  # y >= p
        adv.append((sk.public_raw, msg, b"\x00" * 64))  # zero sig
        want = [sodium.verify_detached(s, m, p) for p, m, s in adv]
        got = bv.verify(adv)
        assert got == want
        assert not any(got)  # everything here must be rejected

    def test_cross_batch_consistency(self, bv):
        """Same item alone and inside a padded batch must agree."""
        sk = SecretKey.pseudo_random_for_testing(3)
        item = (sk.public_raw, b"solo", sk.sign(b"solo"))
        assert bv.verify([item]) == [True]
        batch = [item] * 33
        assert bv.verify(batch) == [True] * 33

    def test_host_assist_split_matches_full_device(self, bv):
        """host_assist peels the batch tail onto a concurrent libsodium
        loop; results must be identical to the all-device path for a mix
        of valid and corrupted signatures."""
        rng = random.Random(77)
        items = []
        for i in range(40):
            sk = SecretKey.pseudo_random_for_testing(200 + i)
            msg = b"assist %d" % i
            sig = bytearray(sk.sign(msg))
            if i % 3 == 0:
                sig[rng.randrange(64)] ^= 1 << rng.randrange(8)
            items.append((sk.public_raw, msg, bytes(sig)))
        want = bv.verify(items)
        ha = BatchVerifier(
            max_batch=64, min_device_batch=16, host_assist=0.4
        )
        got = ha.verify(items)
        assert got == want
        assert ha.n_host_assist_items == 16  # 0.4 * 40 peeled to host

    def test_flush_traced_from_inside(self, bv):
        """A device flush through TpuSigBackend: ``sig.device_flush`` on
        the caller's thread is the cause of everything the guarded worker
        and the stager threads record; a drain has the wait and the
        read-back as children; ``lanes`` counts the buckets dispatched."""
        import threading

        from stellar_tpu.crypto.sigbackend import TpuSigBackend
        from stellar_tpu.trace import NULL_TRACER, Tracer

        items = []
        for i in range(100):  # two chunks of the fixture's 64: the stager pool runs
            sk = SecretKey.pseudo_random_for_testing(500 + i)
            msg = b"traced %d" % i
            items.append((sk.public_raw, msg, sk.sign(msg)))
        be = TpuSigBackend.__new__(TpuSigBackend)  # the fixture's compiled verifier
        be._verifier = bv
        be.cpu_cutover = 0
        be.n_cutover_items = be.n_wedge_fallback_items = 0
        be._wedged_until, be.n_latch_flips = {}, {}
        be._wedge_lock = threading.Lock()
        tr = Tracer()
        be._tracer = bv._tracer = tr
        before = bv.stats()
        try:
            assert be.verify_batch(items) == [True] * 100
            with tr.span("ledger.close", req=41):
                assert be.verify_batch(items[:40]) == [True] * 40
        finally:
            bv._tracer = NULL_TRACER
        after = bv.stats()
        d_items = after["items"] - before["items"]
        d_lanes = after["lanes"] - before["lanes"]
        assert d_items == 140
        assert d_lanes == sum(
            s.attrs["bucket"] for s in tr.spans() if s.name == "ed25519.device_dispatch"
        )
        assert d_lanes >= d_items
        spans = tr.spans()
        by = {s.sid: s for s in spans}
        flushes = [s for s in spans if s.name == "sig.device_flush"]
        assert [(s.attrs["items"], s.attrs["chunks"]) for s in flushes] == [(100, 2), (40, 1)]
        # a flush of its own is its own request (the backend's own count,
        # from 1); one inside a close is the close's
        assert flushes[0].req == 1 and flushes[1].req == 41
        assert be.n_device_flushes == 2 and TpuSigBackend.n_device_flushes == 0
        first = [s for s in spans if s.req == flushes[0].req and s is not flushes[0]]
        names = sorted(s.name for s in first)
        assert names == sorted(
            2 * ["ed25519.host_hash", "ed25519.device_dispatch", "ed25519.upload",
                 "ed25519.drain", "ed25519.wait", "ed25519.readback"]
        )
        # at most 1 + 3 * chunks new spans a flush
        children = ("ed25519.upload", "ed25519.wait", "ed25519.readback")
        assert len([s for s in first if s.name in children]) + 1 == 7
        for s in first:
            if s.name in children:
                # the upload parts its dispatch, wait and read-back their drain
                over = by[s.parent]
                assert over.name == (
                    "ed25519.device_dispatch" if s.name == "ed25519.upload" else "ed25519.drain"
                )
                assert over.start <= s.start and s.end <= over.end
            else:
                # across the worker hop (drain) and the stager pool
                # (host_hash, device_dispatch): threads other than the caller's
                assert s.parent == flushes[0].sid and s.tid != flushes[0].tid

    def test_empty_and_gate_only_batches(self, bv):
        assert bv.verify([]) == []
        # all items fail the host gate -> no device call needed
        calls_before = bv.n_device_calls
        bad = [(b"\x00" * 32, b"m", b"\x00" * 64)] * 3
        assert bv.verify(bad) == [False, False, False]
        assert bv.n_device_calls == calls_before


class TestKernelNames:
    """A kernel's name in a device trace is stated at its ``pallas_call``,
    not inherited from whatever a Python function is called: the
    benchmark finds the verify kernel's device events by it."""

    @staticmethod
    def _pallas_names(jaxpr):
        names = []

        def walk(jp):
            for eqn in jp.eqns:
                if eqn.primitive.name == "pallas_call":
                    names.append(eqn.params["name"])
                for v in eqn.params.values():
                    inner = getattr(v, "jaxpr", None)
                    if inner is not None and hasattr(inner, "eqns"):
                        walk(inner)
                    elif hasattr(v, "eqns"):
                        walk(v)
                    elif isinstance(v, (tuple, list)):
                        for b in v:
                            inner = getattr(b, "jaxpr", b)
                            if hasattr(inner, "eqns"):
                                walk(inner)

        walk(jaxpr.jaxpr)
        return names

    def test_verify_kernel_carries_the_stated_name(self):
        from stellar_tpu.ops import ed25519_pallas as EP

        assert EP.VERIFY_KERNEL_NAME == "verify_kernel_pallas"
        col = jax.ShapeDtypeStruct((32, EP.NT), jnp.uint8)
        jaxpr = jax.make_jaxpr(
            lambda a, r, s, h: EP.verify_kernel_pallas(a, r, s, h, interpret=True)
        )(col, col, col, col)
        assert self._pallas_names(jaxpr) == [EP.VERIFY_KERNEL_NAME]

    @pytest.mark.parametrize("which", ["sha512", "sha256"])
    def test_hash_kernels_carry_their_stated_names(self, which):
        from stellar_tpu.ops.ed25519_pallas import NT

        if which == "sha512":
            from stellar_tpu.ops import sha512 as M

            want = M.SHA512_KERNEL_NAME
            jaxpr = jax.make_jaxpr(lambda p: M.sha512_pallas(p, interpret=True))(
                jax.ShapeDtypeStruct((M.DH_ROWS, NT), jnp.uint8)
            )
        else:
            from stellar_tpu.ops import sha256 as M

            want = M.SHA256_KERNEL_NAME
            jaxpr = jax.make_jaxpr(lambda p, nb: M.sha256_pallas(p, nb, interpret=True))(
                jax.ShapeDtypeStruct((64, NT), jnp.uint8),
                jax.ShapeDtypeStruct((NT,), jnp.int32),
            )
        assert want == which + "_pallas"
        assert self._pallas_names(jaxpr) == [want]


class TestPallasKernel:
    """The Pallas lowering (ops/ed25519_pallas.py) must agree bit-for-bit
    with the XLA verify_kernel — run in interpreter mode on CPU over one
    full tile of mixed valid/corrupt/undecompressable inputs.

    slow (r10 budget triage): 215 s — the single biggest tier-1 line,
    nearly all pallas-interpret compile on CPU hosts (same class as the
    sharded-pallas case below).  The XLA-kernel differentials and the
    RFC 8032 vectors stay in tier-1; the pallas-vs-xla equivalence runs
    in slow/device sessions where the lowering actually executes."""

    @pytest.mark.slow
    def test_pallas_matches_xla_kernel(self):
        import hashlib

        from stellar_tpu.ops.ed25519_pallas import NT, verify_kernel_pallas
        from stellar_tpu.ops.ref25519 import L

        rng = random.Random(42)
        a_b = np.zeros((NT, 32), np.uint8)
        r_b = np.zeros((NT, 32), np.uint8)
        s_b = np.zeros((NT, 32), np.uint8)
        h_b = np.zeros((NT, 32), np.uint8)
        for i in range(NT):
            sk = SecretKey.pseudo_random_for_testing(i)
            msg = b"pallas %d" % i
            sig = bytearray(sk.sign(msg))
            pk = bytearray(sk.public_raw)
            if i % 3 == 1:  # corrupt signature
                sig[rng.randrange(64)] ^= 1 << rng.randrange(8)
            if i % 7 == 3:  # undecompressable / wrong A
                pk[rng.randrange(31)] ^= 1 << rng.randrange(8)
            sig, pk = bytes(sig), bytes(pk)
            h = (
                int.from_bytes(
                    hashlib.sha512(sig[:32] + pk + msg).digest(), "little"
                )
                % L
            )
            a_b[i] = np.frombuffer(pk, np.uint8)
            r_b[i] = np.frombuffer(sig[:32], np.uint8)
            s_b[i] = np.frombuffer(sig[32:], np.uint8)
            h_b[i] = np.frombuffer(h.to_bytes(32, "little"), np.uint8)
        xla_args = (
            jnp.asarray(np.ascontiguousarray(a_b.T).astype(np.int32)),
            jnp.asarray(np.ascontiguousarray(r_b.T).astype(np.int32)),
            jnp.asarray(ed._nibbles_np(s_b)),
            jnp.asarray(ed._nibbles_np(h_b)),
        )
        pallas_args = tuple(
            jnp.asarray(np.ascontiguousarray(x.T))
            for x in (a_b, r_b, s_b, h_b)
        )
        want = np.asarray(jax.jit(ed.verify_kernel)(*xla_args))
        got = np.asarray(verify_kernel_pallas(*pallas_args, interpret=True))
        assert want.sum() > 0 and (~want).sum() > 0  # both classes present
        assert (want == got).all()
        # signed-digit window variant: identical results on the same tile
        got_signed = np.asarray(
            verify_kernel_pallas(*pallas_args, interpret=True, signed=True)
        )
        assert (want == got_signed).all()

    def test_batch_gate_matches_scalar_gate(self):
        """strict_input_ok_batch must accept exactly what strict_input_ok
        accepts — valid sigs, s >= L, small-order R/A, non-canonical A."""
        from stellar_tpu.ops import ref25519 as ref

        rng = random.Random(5)
        pks, sigs = [], []
        sk = SecretKey.pseudo_random_for_testing(1)
        good_sig = sk.sign(b"x")
        for e in ref.small_order_blacklist():
            pks.append(e)
            sigs.append(good_sig)
            pks.append(sk.public_raw)
            sigs.append(e + good_sig[32:])
        bad_s = (int.from_bytes(good_sig[32:], "little") + ref.L).to_bytes(
            32, "little"
        )
        pks.append(sk.public_raw)
        sigs.append(good_sig[:32] + bad_s)
        pks.append((2**255 - 5).to_bytes(32, "little"))
        sigs.append(good_sig)
        for i in range(64):
            k = SecretKey.pseudo_random_for_testing(100 + i)
            sg = bytearray(k.sign(b"m%d" % i))
            if i % 2:
                sg[rng.randrange(64)] ^= 1 << rng.randrange(8)
            pks.append(k.public_raw)
            sigs.append(bytes(sg))
        want = [ref.strict_input_ok(p, s) for p, s in zip(pks, sigs)]
        got = ref.strict_input_ok_batch(
            np.frombuffer(b"".join(pks), np.uint8).reshape(-1, 32),
            np.frombuffer(b"".join(sigs), np.uint8).reshape(-1, 64),
        )
        assert got.tolist() == want


class TestPipelineAbort:
    def test_mid_pipeline_dispatch_error_raises_not_deadlocks(self):
        """BatchVerifier.verify's multi-chunk pipeline bounds in-flight
        device buffers with a semaphore; a dispatch error mid-stream must
        RAISE to the caller (with the stager unblocked), never deadlock
        in the executor teardown (ed25519.py:399-427)."""
        import threading

        from stellar_tpu.ops.verifier import BatchVerifier

        bv = BatchVerifier(max_batch=16)  # small chunks -> many of them
        calls = []

        def flaky(staged):
            # hermetic: successful dispatches are stubbed (no jit compile,
            # no 60s cold-cache dependency); only the error path is real
            calls.append(1)
            if len(calls) == 3:
                raise RuntimeError("device dropped mid-stream")
            return np.ones(16, dtype=bool)

        bv._dispatch_staged = flaky
        items = []
        for i in range(16 * 6):  # 6 chunks through PIPELINE_DEPTH=2
            sk = SecretKey.pseudo_random_for_testing(i)
            msg = b"pipeline %d" % i
            items.append((sk.public_raw, msg, sk.sign(msg)))
        outcome = []

        def run():
            try:
                bv.verify(items)
                outcome.append(("returned", None))
            except BaseException as e:  # surfaced in the main thread below
                outcome.append(("raised", e))

        t = threading.Thread(target=run, daemon=True)
        t.start()
        t.join(30)
        assert outcome, "pipeline deadlocked instead of raising"
        kind, exc = outcome[0]
        assert kind == "raised", f"verify() {kind} instead of raising"
        assert isinstance(exc, RuntimeError) and "mid-stream" in str(exc), exc


class TestShardedVerifier:
    """End-to-end make_sharded_verifier over the 8-device CPU mesh that
    conftest.py sets up — the multi-chip data-parallel path the driver's
    dryrun_multichip validates (stellar_tpu/parallel/mesh.py)."""

    def test_sharded_verifier_on_8_device_mesh(self):
        from stellar_tpu.parallel.mesh import make_mesh, make_sharded_verifier

        devs = jax.devices()
        assert len(devs) >= 8, "conftest must provide 8 virtual CPU devices"
        mesh = make_mesh(devs[:8], axis="batch")
        bv = make_sharded_verifier(
            mesh=mesh, max_batch=64, min_device_batch=16
        )
        rng = random.Random(77)
        items = []
        want = []
        for i in range(40):
            sk = SecretKey.pseudo_random_for_testing(100 + i)
            msg = bytes([rng.randrange(256) for _ in range(16)])
            sig = bytearray(sk.sign(msg))
            if i % 3 == 0:
                sig[rng.randrange(64)] ^= 1 << rng.randrange(8)
            items.append((sk.public_raw, msg, bytes(sig)))
            want.append(sodium.verify_detached(bytes(sig), msg, sk.public_raw))
        assert bv.verify(items) == want
        assert bv.n_device_calls == 1  # one coalesced sharded dispatch

    @pytest.mark.slow
    def test_sharded_pallas_verifier_on_mesh(self):
        """backend="pallas" with a mesh runs the Pallas kernel PER SHARD
        under shard_map (interpreter mode on the CPU mesh) — the multi-
        chip path that keeps the fast kernel on real TPU pods.  Two
        devices bound the interpret cost (granule = 2*NT lanes).

        slow: shard_map × pallas-interpret compiles for minutes on CPU
        hosts — it would eat the tier-1 budget, so it runs only when slow
        tests are selected (real-TPU runs compile it with Mosaic quickly)."""
        from stellar_tpu.ops.verifier import BatchVerifier
        from stellar_tpu.ops.ed25519_pallas import NT
        from stellar_tpu.parallel.mesh import make_mesh

        devs = jax.devices()
        assert len(devs) >= 2
        mesh = make_mesh(devs[:2], axis="batch")
        bv = BatchVerifier(max_batch=2 * NT, mesh=mesh, backend="pallas")
        assert bv._granule == 2 * NT
        # an awkward min_device_batch must still bucket to whole tiles
        odd = BatchVerifier(
            max_batch=4 * NT, mesh=mesh, backend="pallas",
            min_device_batch=3 * NT,
        )
        assert odd._bucket(1) % odd._granule == 0
        items, expect = [], []
        for i in range(40):
            sk = SecretKey.pseudo_random_for_testing(700 + i)
            msg = b"shardmap %d" % i
            sig = sk.sign(msg)
            if i % 4 == 1:
                sig = sig[:13] + bytes([sig[13] ^ 1]) + sig[14:]
                expect.append(False)
            else:
                expect.append(True)
            items.append((sk.public_raw, msg, sig))
        assert bv.verify(items) == expect
        assert bv.n_device_calls == 1

    def _mixed_hostile_items(self, n, seed):
        """Mixed lanes spanning BOTH rejection planes: valid / corrupt-R /
        corrupt-s (device-reject) and hostile-s (s >= L) / small-order A /
        malformed length (host-gate reject) — the lane mix the sharded
        and unsharded dispatch paths must agree on exactly."""
        rng = random.Random(seed)
        items, want = [], []
        for i in range(n):
            sk = SecretKey.pseudo_random_for_testing(900 + i)
            msg = b"mesh diff %d" % i
            pk, sig = sk.public_raw, bytearray(sk.sign(msg))
            if i % 6 == 1:
                sig[rng.randrange(32)] ^= 1 << rng.randrange(8)  # R
            elif i % 6 == 2:
                sig[32] ^= 1  # s low byte, stays canonical
            elif i % 6 == 3:  # hostile s >= L: host gate rejects
                sig[32:] = (
                    int.from_bytes(bytes(sig[32:]), "little") + ref.L
                ).to_bytes(32, "little")
            elif i % 6 == 4:  # small-order A: host gate rejects
                bl = ref.small_order_blacklist()
                pk = bl[i % len(bl)]
            elif i % 6 == 5:  # malformed signature length
                sig = sig[:40]
            sig = bytes(sig)
            items.append((pk, msg, sig))
            want.append(
                len(sig) == 64 and sodium.verify_detached(sig, msg, pk)
            )
        return items, want

    def test_sharded_matches_unsharded_mixed_hostile_remainder(self):
        """Bit-exact verdicts sharded-vs-unsharded-vs-libsodium on mixed
        valid/invalid/hostile-s lanes, with the live-lane count NOT
        divisible by the mesh width (43 % 8 != 0): the tail shard pads
        and two shards are dead — the pad-and-mask remainder path."""
        from stellar_tpu.parallel.mesh import make_mesh

        devs = jax.devices()
        mesh = make_mesh(devs[:8])
        sbv = BatchVerifier(max_batch=64, mesh=mesh, min_device_batch=16)
        ubv = BatchVerifier(max_batch=64, min_device_batch=16)
        items, want = self._mixed_hostile_items(43, seed=11)
        got_s = sbv.verify(items)
        got_u = ubv.verify(items)
        assert got_s == want
        assert got_u == want
        assert sbv.n_gate_rejects == ubv.n_gate_rejects > 0
        assert sbv.n_device_calls == 1  # one coalesced sharded dispatch

    def test_sharded_pipeline_multichunk_gate_skip(self):
        """Multi-chunk sharded pipeline (3 chunks through the stager
        threads): verdicts identical to the unsharded pipeline AND an
        all-gate-rejected chunk skips its device dispatch on both paths
        (hostile floods never reach the chips)."""
        from stellar_tpu.parallel.mesh import make_mesh

        devs = jax.devices()
        mesh = make_mesh(devs[:8])
        sbv = BatchVerifier(max_batch=64, mesh=mesh, min_device_batch=16)
        ubv = BatchVerifier(max_batch=64, min_device_batch=16)
        items, want = self._mixed_hostile_items(192, seed=23)
        # chunk 2 (items 64:128) becomes pure hostile-s: every lane fails
        # the host strict gate, so that chunk must never dispatch
        sk = SecretKey.pseudo_random_for_testing(555)
        msg = b"flood"
        sig = sk.sign(msg)
        hostile = sig[:32] + (
            int.from_bytes(sig[32:], "little") + ref.L
        ).to_bytes(32, "little")
        for j in range(64, 128):
            items[j] = (sk.public_raw, msg, hostile)
            want[j] = False
        got_s = sbv.verify(items)
        got_u = ubv.verify(items)
        assert got_s == want
        assert got_u == want
        assert sbv.n_device_calls == 2  # chunks 1 and 3 only
        assert ubv.n_device_calls == 2

    @pytest.mark.slow
    def test_sharded_non_pow2_mesh_width(self):
        """A 3-device mesh (non-pow2): buckets stay whole multiples of
        the width and remainders pad-and-mask.  slow: the 3-way GSPMD
        partition is a new XLA compile shape on CPU hosts."""
        from stellar_tpu.parallel.mesh import make_mesh

        devs = jax.devices()
        assert len(devs) >= 3
        mesh = make_mesh(devs[:3])
        bv = BatchVerifier(max_batch=48, mesh=mesh, min_device_batch=3)
        assert bv.max_batch % 3 == 0
        items, want = self._mixed_hostile_items(40, seed=37)
        assert bv.verify(items) == want

    def test_dryrun_multichip_entrypoint(self):
        """The driver-facing entry must succeed regardless of caller env."""
        import sys
        import pathlib

        sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
        try:
            import __graft_entry__ as g

            g.dryrun_multichip(8)
        finally:
            sys.path.pop(0)


class TestMultiStream:
    @pytest.mark.slow
    def test_two_stream_pipeline_matches_single(self):
        """streams=2 runs two stage+dispatch workers (upload/execute
        overlap on a pipelining transport); results and ordering must be
        identical to the classic 1-stream pipeline, including scattered
        gate rejects.

        slow (r10 budget triage): ~90 s of XLA-CPU compile for a
        device-only dispatch mode — stream overlap is meaningless off
        the real transport, and the 1-stream BatchVerifier differentials
        keep the verify plane covered in tier-1."""
        from stellar_tpu.ops.verifier import BatchVerifier

        items = []
        for i in range(16 * 5):  # 5 chunks
            sk = SecretKey.pseudo_random_for_testing(i)
            msg = b"stream test %d" % i
            items.append((sk.public_raw, msg, sk.sign(msg)))
        # corrupt a few spread across chunks; one malformed length
        items[3] = (items[3][0], items[3][1], b"\x00" * 64)
        items[40] = (items[40][0], b"wrong msg", items[40][2])
        items[70] = (items[70][0][:31], items[70][1], items[70][2])

        bv1 = BatchVerifier(max_batch=16, streams=1)
        bv2 = BatchVerifier(max_batch=16, streams=2)
        out1 = bv1.verify(items)
        out2 = bv2.verify(items)
        assert out1 == out2
        assert not out2[3] and not out2[40] and not out2[70]
        assert sum(out2) == len(items) - 3

    def test_streams_resolve_as_passed(self):
        from stellar_tpu.ops.verifier import BatchVerifier

        assert BatchVerifier(max_batch=16).streams == 1
        assert BatchVerifier(max_batch=16, streams=3).streams == 3
        assert BatchVerifier(max_batch=16, streams=0).streams == 1

    def test_streams_plumbs_through_sig_backend(self):
        from stellar_tpu.crypto.sigbackend import TpuSigBackend

        be = TpuSigBackend(max_batch=16, streams=2)
        assert be._verifier.streams == 2

    def test_out_of_order_staging_cannot_deadlock(self):
        """With streams=2, a later chunk staging FASTER than an earlier one
        once deadlocked the pipeline (the later chunk's worker stole the
        last in-flight permit while the main thread blocked on the earlier
        chunk's future).  The in-flight bound now lives in a main-thread
        submission counter; this pins the fix by making every even chunk
        stage slowly."""
        import threading

        import numpy as np

        from stellar_tpu.ops.verifier import BatchVerifier

        bv = BatchVerifier(max_batch=16, streams=2)
        real_stage = bv._stage_chunk
        idx_lock = threading.Lock()
        seen = []

        def slow_even_stage(items, start, n):
            with idx_lock:
                i = len(seen)
                seen.append(i)
            if i % 2 == 0:
                import time

                time.sleep(0.05)  # even chunks stage slower than odd ones
            return real_stage(items, start, n)

        bv._stage_chunk = slow_even_stage
        bv._dispatch_staged = lambda staged: np.ones(
            0 if staged is None else staged.packed.shape[1], dtype=bool
        )
        items = []
        for i in range(16 * 8):  # 8 chunks through both streams
            sk = SecretKey.pseudo_random_for_testing(i)
            msg = b"deadlock probe %d" % i
            items.append((sk.public_raw, msg, sk.sign(msg)))
        outcome = []

        def run():
            outcome.append(bv.verify(items))

        t = threading.Thread(target=run, daemon=True)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive(), "2-stream pipeline deadlocked"
        assert outcome and all(outcome[0])
