"""Device-resident verify hash stage (ISSUE r16).

Three layers, mirroring the PR:
1. ops/sha512.py — the batched single-block SHA-512 + fold-at-2^252
   mod-L stage, differential against hashlib + Python bigints (and the
   native/sighash.c oracle where built) across the block-boundary lanes;
2. BatchVerifier(device_hash=True) — end-to-end verdicts bit-exact with
   libsodium AND the host-hash path on every lane class: 95/96/111/112-
   byte preimages, the multi-block residual routing, hostile-s (s >= L),
   all-reject chunks skipping dispatch, mesh remainder chunks, and the
   no-toolchain staging fallback;
3. the torsion-proof plane — verify(A:=P, h:=L, s:=0, R:=identity) on
   the device batch plane vs ref25519.is_torsion_free, plus the backend
   surface (cutover/wedge) and the aggregate scheme's fresh-R routing.

Compile budget: the device-hash kernels are NEW XLA shapes; everything
shares one unsharded (160, 64) bucket and one 8-device sharded bucket
via class-scoped fixtures, and the pallas-interpret parity leg rides
``-m slow`` per the r10 budget policy.
"""

import hashlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from stellar_tpu.crypto import SecretKey, sodium  # noqa: E402
from stellar_tpu.ops import ref25519 as ref  # noqa: E402
from stellar_tpu.ops import sha512 as dsha  # noqa: E402
from stellar_tpu.ops.ref25519 import L  # noqa: E402
from stellar_tpu.ops.verifier import BatchVerifier  # noqa: E402

pytestmark = pytest.mark.tpu_kernel


def _valid_items(n, seed=91000, mlens=(0, 1, 31, 32, 46, 47, 48, 64, 200)):
    """(pk, msg, sig) triples whose message lengths sweep the single/
    multi-block boundary: preimage = 64 + mlen bytes, so mlen 31/32
    bracket 95/96 (the dominant class) and 47/48 bracket 111/112 (the
    single-block limit)."""
    items = []
    for i in range(n):
        sk = SecretKey.pseudo_random_for_testing(seed + i)
        mlen = mlens[i % len(mlens)]
        msg = bytes((seed + i + j) % 256 for j in range(mlen))
        items.append((sk.public_raw, msg, sk.sign(msg)))
    return items


def _hostile_items(seed=92000):
    sk = SecretKey.pseudo_random_for_testing(seed)
    msg = b"hostile lane"
    pk, sig = sk.public_raw, sk.sign(msg)
    bad_r = bytearray(sig)
    bad_r[3] ^= 0x10
    return [
        (pk, msg, sig[:32] + L.to_bytes(32, "little")),        # s = L
        (pk, msg, sig[:32] + (L + 7).to_bytes(32, "little")),  # s > L
        (pk, msg, sig[:32] + (2**256 - 1).to_bytes(32, "little")),
        (pk, b"different message", sig),                       # wrong msg
        (pk, msg, bytes(bad_r)),                               # corrupt R
        (bytes(32), msg, sig),                                 # small-order A
        (pk[:31], msg, sig),                                   # short pk
        (pk, msg, sig[:63]),                                   # short sig
        (pk, msg, sig),                                        # valid control
    ]


class TestDeviceSha512:
    """Layer 1: the hash stage itself, against hashlib + bigints."""

    @pytest.fixture(scope="class")
    def h_fn(self):
        return jax.jit(dsha.h_rows_from_packed)

    @staticmethod
    def _pack(lanes):
        """lanes: list of (r, a, m) -> packed (160, n) uint8 columns with
        flag=1 (device hash)."""
        p = np.zeros((dsha.DH_ROWS, len(lanes)), dtype=np.uint8)
        for j, (r, a, m) in enumerate(lanes):
            p[0:32, j] = np.frombuffer(a, np.uint8)
            p[32:64, j] = np.frombuffer(r, np.uint8)
            if m:
                p[dsha.ROW_M : dsha.ROW_M + len(m), j] = np.frombuffer(
                    m, np.uint8
                )
            p[dsha.ROW_MLEN, j] = len(m)
            p[dsha.ROW_FLAG, j] = 1
        return p

    def test_single_block_boundaries_vs_hashlib(self, h_fn):
        rng = np.random.default_rng(7)
        lanes, expect = [], []
        for mlen in (0, 1, 2, 31, 32, 33, 46, 47):
            for _ in range(3):
                r = rng.integers(0, 256, 32, dtype=np.uint8).tobytes()
                a = rng.integers(0, 256, 32, dtype=np.uint8).tobytes()
                m = rng.integers(0, 256, mlen, dtype=np.uint8).tobytes()
                lanes.append((r, a, m))
                h = (
                    int.from_bytes(
                        hashlib.sha512(r + a + m).digest(), "little"
                    )
                    % L
                )
                expect.append(
                    np.frombuffer(h.to_bytes(32, "little"), np.uint8)
                )
        out = np.asarray(h_fn(jnp.asarray(self._pack(lanes))))
        assert (out == np.stack(expect, axis=1).astype(np.int32)).all()

    def test_flag0_lanes_pass_host_h_through(self, h_fn):
        """flag=0 (multi-block residual / torsion columns): rows 96:128
        come back verbatim — the device hash is bypassed by selection."""
        rng = np.random.default_rng(8)
        p = np.zeros((dsha.DH_ROWS, 8), dtype=np.uint8)
        p[0:96] = rng.integers(0, 256, (96, 8), dtype=np.uint8)
        hostile_h = rng.integers(0, 256, (32, 8), dtype=np.uint8)
        p[96:128] = hostile_h
        out = np.asarray(h_fn(jnp.asarray(p)))
        assert (out == hostile_h.astype(np.int32)).all()

    def test_mod_l_reduction_edges(self):
        """The fold-at-2^252 reduction on crafted 512-bit values: 0, 1,
        L±1, L, 2^252, k*L, all-ones — plus random, vs Python bigints
        (and the native reduce512_le oracle where built)."""
        vals = [
            0, 1, L - 1, L, L + 1, 1 << 252, (1 << 252) - 1, 8 * L,
            (1 << 512) - 1, ((1 << 512) // L) * L, ((1 << 385) // L) * L,
        ]
        rng = np.random.default_rng(9)
        vals += [
            int.from_bytes(rng.bytes(64), "little") for _ in range(32)
        ]
        d = np.zeros((64, len(vals)), dtype=np.int32)
        for j, v in enumerate(vals):
            d[:, j] = np.frombuffer(v.to_bytes(64, "little"), np.uint8)

        def reduce_rows(dd):
            return jnp.stack(dsha._mod_l_rows([dd[i] for i in range(64)]))

        out = np.asarray(jax.jit(reduce_rows)(jnp.asarray(d)))
        from stellar_tpu import native

        mod = native.load_sighash()
        for j, v in enumerate(vals):
            want = (v % L).to_bytes(32, "little")
            assert bytes(out[:, j].astype(np.uint8)) == want, f"value #{j}"
            if mod is not None:
                assert mod._reduce512(v.to_bytes(64, "little")) == want

    def test_native_stage_raw_vs_python_fallback(self):
        """The C stage_raw buffer is byte-identical to _stage_py's on
        valid, hostile, malformed-length and residual lanes (toolchain-less
        hosts run the Python twin, so the layouts must agree exactly)."""
        from stellar_tpu import native

        mod = native.load_sighash()
        if mod is None:
            pytest.skip("native sighash not built")
        items = _valid_items(24) + _hostile_items()
        n = len(items)
        from stellar_tpu.ops.verifier import _BLACKLIST

        c_out = np.zeros((dsha.DH_ROWS, n + 3), dtype=np.uint8)
        c_ok = np.zeros(n, dtype=np.uint8)
        rej_c = mod.stage_raw(items, 0, n, c_out, c_ok, _BLACKLIST)
        bv = BatchVerifier.__new__(BatchVerifier)
        bv.device_hash = True
        py_out = np.ones((dsha.DH_ROWS, n + 3), dtype=np.uint8)
        py_ok = np.zeros(n, dtype=np.uint8)
        rej_py = bv._stage_py(items, 0, n, py_out, py_ok)
        assert rej_c == rej_py
        assert (c_ok == py_ok).all()
        assert (c_out == py_out).all()


class TestDeviceHashVerifier:
    """Layer 2: end-to-end BatchVerifier(device_hash=True) verdicts."""

    @pytest.fixture(scope="class")
    def bvs(self):
        # min_device_batch=64 pins EVERY dispatch in this module to the
        # one (rows, 64) bucket per layout — no extra XLA compile shapes
        host = BatchVerifier(
            max_batch=64, min_device_batch=64, device_hash=False
        )
        dev = BatchVerifier(
            max_batch=64, min_device_batch=64, device_hash=True
        )
        return host, dev

    def test_boundary_and_residual_lanes_match_libsodium(self, bvs):
        host, dev = bvs
        items = _valid_items(36) + _hostile_items()
        want = [
            sodium.verify_detached(sig, msg, pk) for pk, msg, sig in items
        ]
        assert host.verify(items) == want
        assert dev.verify(items) == want
        # the residual class actually routed through flag=0 lanes (a
        # staged chunk with mlen > 47 must not starve the differential)
        assert any(len(m) > dsha.MAX_DEVICE_MSG for _, m, _ in items)

    def test_all_reject_chunk_skips_dispatch(self, bvs):
        _, dev = bvs
        calls = dev.n_device_calls
        out = dev.verify([(b"", b"m", b"") for _ in range(8)])
        assert out == [False] * 8
        assert dev.n_device_calls == calls
        assert dev.n_gate_rejects >= 8

    def test_python_staging_fallback_bit_exact(self, bvs):
        """native_hash=False pins the numpy/hashlib raw staging — the
        no-toolchain twin must produce identical verdicts (it shares the
        compiled kernel, so only staging differs)."""
        host, dev = bvs
        py = BatchVerifier(
            max_batch=64,
            min_device_batch=64,
            device_hash=True,
            native_hash=False,
        )
        py._programs.kernel = dev._programs.kernel
        items = _valid_items(20, seed=93000) + _hostile_items()
        want = [
            sodium.verify_detached(sig, msg, pk) for pk, msg, sig in items
        ]
        assert py.verify(items) == want

    def test_knob_off_keeps_128_row_layout(self, bvs):
        host, dev = bvs
        assert host.device_hash is False and host._rows == 128
        assert dev.device_hash is True and dev._rows == dsha.DH_ROWS
        assert host.stats()["device_hash"] is False
        assert dev.stats()["device_hash"] is True


class TestDeviceHashSharded:
    """Layer 2b: the mesh path — per-chip raw staging (no per-chip C
    hash pass), remainder chunks padding the tail shard."""

    @pytest.fixture(scope="class")
    def bv_mesh(self):
        from stellar_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(jax.devices()[:8])
        return BatchVerifier(
            max_batch=64, min_device_batch=64, mesh=mesh, device_hash=True
        )

    def test_sharded_remainder_mixed_lanes(self, bv_mesh):
        # 43 % 8 != 0: the tail shard pads, dead shards stage nothing
        items = (_valid_items(43, seed=95000) + _hostile_items())[:43]
        want = [
            sodium.verify_detached(sig, msg, pk) for pk, msg, sig in items
        ]
        assert bv_mesh.verify(items) == want
        assert bv_mesh.stats()["mesh_devices"] == 8
        assert bv_mesh.stats()["device_hash"] is True

    def test_sharded_torsion_remainder(self, bv_mesh):
        B = ref.base_point()
        encs = [ref.compress(ref.scalar_mult(k, B)) for k in range(1, 20)]
        encs += [bytes(e) for e in ref.small_order_blacklist()][:3]
        got = bv_mesh.verify_torsion(encs)
        exp = []
        for e in encs:
            pt = ref.decompress(e) if ref.fe_is_canonical(e) else None
            exp.append(pt is not None and ref.is_torsion_free(pt))
        assert got == exp


class TestTorsionDevicePlane:
    """Layer 3: [L]·P == identity on the batch plane vs the ref oracle,
    and the backend/scheme surfaces above it."""

    @pytest.fixture(scope="class")
    def bv(self):
        # shares the (160, 64) device-hash bucket shape — but its own
        # instance so torsion counters start clean
        return BatchVerifier(
            max_batch=64, min_device_batch=64, device_hash=True
        )

    def _cases(self):
        B = ref.base_point()
        prime = [ref.compress(ref.scalar_mult(k, B)) for k in (1, 2, 7, 7919)]
        ident = ref.compress(ref.IDENT)
        tors = [bytes(e) for e in ref.small_order_blacklist()]
        # mixed-torsion: prime-order + 8-torsion component — the exact
        # inputs the aggregate soundness fix exists for
        mixed = []
        for e in tors:
            pt = ref.decompress(e)
            if pt is not None and not ref.point_equal(pt, ref.IDENT):
                mixed.append(
                    ref.compress(ref.point_add(ref.scalar_mult(3, B), pt))
                )
        malformed = [b"", b"short", b"\xff" * 32, b"\x00" * 31]
        return prime + [ident] + tors + mixed[:3] + malformed

    def test_device_matches_host_oracle(self, bv):
        encs = self._cases()
        got = bv.verify_torsion(encs)
        exp = []
        for e in encs:
            if len(e) != 32 or not ref.fe_is_canonical(e):
                exp.append(False)
                continue
            pt = ref.decompress(e)
            exp.append(pt is not None and ref.is_torsion_free(pt))
        assert got == exp
        # and the halfagg host surface agrees lane-for-lane
        from stellar_tpu.crypto.aggregate import halfagg

        assert halfagg.torsion_free_encs(encs) == exp

    def test_backend_surface_cutover_and_device(self, bv):
        from stellar_tpu.crypto.sigbackend import (
            CachingSigBackend,
            TpuSigBackend,
        )
        from stellar_tpu.crypto.sigcache import VerifySigCache

        encs = self._cases()
        from stellar_tpu.crypto.aggregate import halfagg

        exp = halfagg.torsion_free_encs(encs)
        # cutover: small batches ride the host ladder
        tb = TpuSigBackend.__new__(TpuSigBackend)
        tb._verifier = bv
        tb.cpu_cutover = 10_000
        tb.n_cutover_items = tb.n_cutover_torsion = 0
        tb.n_wedge_fallback_items = 0
        tb._wedged_until, tb.n_latch_flips = {}, {}
        import threading

        tb._wedge_lock = threading.Lock()
        before = bv.n_torsion_items
        assert tb.torsion_check(encs) == exp
        assert bv.n_torsion_items == before  # host path: no device items
        assert tb.n_cutover_torsion == len(encs)
        # device: cutover 0 forces the batch plane
        tb.cpu_cutover = 0
        assert tb.torsion_check(encs) == exp
        assert bv.n_torsion_items == before + len(encs)
        # the caching wrapper delegates (no verdict cache involvement)
        cb = CachingSigBackend(tb, VerifySigCache())
        assert cb.torsion_check(encs) == exp

    def test_scheme_routes_fresh_r_proofs_to_device(self, bv):
        """HalfAggScheme end-to-end on a single-slot storm: verdicts
        bit-identical to the per-envelope reference scheme, with the
        post-MSM fresh-R proofs served by the device batch plane."""
        from stellar_tpu.crypto.sigbackend import (
            CachingSigBackend,
            TpuSigBackend,
            make_backend,
        )
        from stellar_tpu.crypto.aggregate.scheme import (
            HalfAggScheme,
            ScpSigScheme,
        )
        from stellar_tpu.crypto.sigcache import VerifySigCache

        be = make_backend(
            "tpu",
            cache=VerifySigCache(),
            max_batch=64,
            cpu_cutover=0,
            device_hash=True,
        )
        # share the already-compiled kernel + bucket shape (budget policy)
        be.inner._verifier._programs.kernel = bv._programs.kernel
        be.inner._verifier.min_device_batch = 64
        items, slots = [], []
        for i in range(12):
            sk = SecretKey.pseudo_random_for_testing(96000 + i)
            msg = b"storm ballot %04d" % (i % 3)
            items.append((sk.public_raw, msg, sk.sign(msg)))
            slots.append(77)
        # poisoned twin: one corrupted s in the bucket
        poisoned = list(items)
        pk, m, s = poisoned[5]
        b = bytearray(s)
        b[40] ^= 1
        poisoned[5] = (pk, m, bytes(b))

        ref_sch = ScpSigScheme(
            make_backend("cpu", cache=VerifySigCache()), VerifySigCache()
        )
        sch = HalfAggScheme(be, VerifySigCache())
        assert sch.verify_flush(items, slots) == ref_sch.verify_flush(
            items, slots
        )
        assert sch.n_r_proof_points == len(items)
        assert sch.stats()["r_proof_points"] == len(items)
        assert be.inner._verifier.n_torsion_items >= len(items)
        sch2 = HalfAggScheme(be, VerifySigCache())
        assert sch2.verify_flush(poisoned, slots) == ref_sch.verify_flush(
            poisoned, slots
        )


class TestConfigAndWiring:
    def test_config_knob_default_and_validation(self):
        from stellar_tpu.main.config import Config

        cfg = Config()
        assert cfg.DEVICE_HASH is False
        cfg.validate()
        for good in (True, False, 0, 1):
            cfg.DEVICE_HASH = good
            cfg.validate()
        for bad in ("yes", 2, -1, 1.5, [1]):
            cfg.DEVICE_HASH = bad
            with pytest.raises(ValueError):
                cfg.validate()

    def test_config_from_dict_plumbs(self):
        from stellar_tpu.main.config import Config

        cfg = Config.from_dict({"DEVICE_HASH": True})
        assert cfg.DEVICE_HASH is True

    def test_make_backend_plumbs_device_hash(self):
        from stellar_tpu.crypto.sigbackend import make_backend
        from stellar_tpu.crypto.sigcache import VerifySigCache

        be = make_backend(
            "tpu", cache=VerifySigCache(), max_batch=64, device_hash=True
        )
        assert be.inner._verifier.device_hash is True
        assert be.stats()["device_hash"] is True
        # default stays off (the SIG_MESH opt-in pattern)
        be_off = make_backend("tpu", cache=VerifySigCache(), max_batch=64)
        assert be_off.inner._verifier.device_hash is False

    def test_device_hash_resolves_as_passed(self, monkeypatch):
        # resolution only — the kernel build is stubbed out so no compile
        # shape is added
        monkeypatch.setattr(BatchVerifier, "_make_kernel", lambda self, batch_inv: None)
        bv = BatchVerifier(max_batch=64, device_hash=True)
        assert bv.device_hash is True and bv._rows == dsha.DH_ROWS
        assert bv.stats()["device_hash"] is True and bv._programs.fields(64)["rows"] == dsha.DH_ROWS
        bv = BatchVerifier(max_batch=64)
        assert bv.device_hash is False and bv._rows == 128
        assert bv.stats()["device_hash"] is False and bv._programs.fields(64)["rows"] == 128


@pytest.mark.slow
class TestPallasParity:
    """The Pallas sha stage (interpret mode) against the XLA lowering —
    device-shaped compile cost on a CPU host, slow-marked per the r10
    budget policy; the Mosaic-compiled stage runs on the chip in
    chip_smoke.py's kernel leg."""

    def test_sha512_pallas_matches_xla(self):
        from stellar_tpu.ops.ed25519_pallas import NT
        from stellar_tpu.ops.sha512 import sha512_pallas

        rng = np.random.default_rng(11)
        packed = np.zeros((dsha.DH_ROWS, NT), dtype=np.uint8)
        for j in range(NT):
            mlen = j % (dsha.MAX_DEVICE_MSG + 1)
            packed[0:64, j] = rng.integers(0, 256, 64, dtype=np.uint8)
            packed[dsha.ROW_M : dsha.ROW_M + mlen, j] = rng.integers(
                0, 256, mlen, dtype=np.uint8
            )
            packed[dsha.ROW_MLEN, j] = mlen
            packed[dsha.ROW_FLAG, j] = 1 if j % 5 else 0
        p = jnp.asarray(packed)
        xla = np.asarray(jax.jit(dsha.h_rows_from_packed)(p))
        pal = np.asarray(sha512_pallas(p, interpret=True))
        assert (xla == pal).all()
