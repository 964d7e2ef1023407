"""Cold-clone build parity for the native C engines (tier-1).

A fresh checkout carries only the .c sources — the .so files are built on
first use.  Until now that path was only validated by hand (PROFILE.md
round-5 "cold-clone validation"); this builds all FIVE extensions from
source in a temp dir with the system toolchain and runs a smoke
differential of each against the checked-in/loaded behavior, so a
toolchain or source regression that would only bite a cold clone fails
tier-1 instead."""

import ctypes
import hashlib
import os
import shutil

import numpy as np
import pytest

from stellar_tpu import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="no C toolchain"
)


@pytest.fixture(scope="module")
def cold_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("coldbuild")
    src_dir = os.path.dirname(os.path.abspath(native.__file__))
    for name in (
        "bucketmerge.c", "cxdrpack.c", "sighash.c", "halfagg.c", "applycore.c",
    ):
        shutil.copy(os.path.join(src_dir, name), str(d / name))
    return d


def test_bucketmerge_cold_build_and_sha256(cold_dir):
    so = str(cold_dir / "_bucketmerge_cold.so")
    assert native._compile_so(str(cold_dir / "bucketmerge.c"), so), (
        "bucketmerge.c failed to compile from source"
    )
    lib = ctypes.CDLL(so)
    lib.sha256_file.restype = ctypes.c_int
    lib.sha256_file.argtypes = [ctypes.c_char_p, ctypes.c_char * 32]
    data = b"cold-clone parity \x00\xff" * 700
    path = cold_dir / "data.bin"
    path.write_bytes(data)
    out = (ctypes.c_char * 32)()
    assert lib.sha256_file(str(path).encode(), out) == 0
    assert bytes(out) == hashlib.sha256(data).digest()
    # same answer as the checked-in/loaded engine
    assert bytes(out) == native.sha256_file(str(path))


def test_staleness_follows_source_content_not_mtime(tmp_path):
    """A copied tree has arbitrary mtimes: only a change of the source
    bytes (or of the flags) may trigger a rebuild, and a .so whose stamp
    is missing is never trusted."""
    src = tmp_path / "probe.c"
    so = str(tmp_path / "probe.so")
    src.write_text("int probe(int x) { return x + 1; }\n")
    assert native._needs_build(str(src), so)
    assert native._compile_so(str(src), so)
    assert not native._needs_build(str(src), so)
    # source newer than the .so, and far older: neither is stale
    os.utime(src, (2_000_000_000, 2_000_000_000))
    assert not native._needs_build(str(src), so)
    os.utime(src, (1, 1))
    assert not native._needs_build(str(src), so)
    assert native._needs_build(str(src), so, ("-O3",))
    src.write_text("int probe(int x) { return x + 2; }\n")
    os.utime(src, (1, 1))  # older than the .so, yet stale by content
    assert native._needs_build(str(src), so)
    assert native._compile_so(str(src), so)
    assert ctypes.CDLL(so).probe(1) == 3
    os.unlink(so + ".srchash")
    assert native._needs_build(str(src), so)


def test_cxdrpack_cold_build_pack_differential(cold_dir):
    # the module name must match the source's PyInit symbol; loading the
    # SAME name from a different path yields a distinct fresh module
    cold = native._load_extension(
        "_cxdrpack", str(cold_dir / "cxdrpack.c"),
        str(cold_dir / "_cxdrpack.so"),
    )
    assert cold is not None, "cxdrpack.c failed to compile from source"
    import random

    from stellar_tpu.xdr.arbitrary import arbitrary_of
    from stellar_tpu.xdr.base import XdrError, _cspec_of
    from stellar_tpu.xdr.entries import LedgerEntry

    defs = []
    root = _cspec_of(LedgerEntry._codec, defs, {})
    prog = cold.compile(defs, root, XdrError)
    for i in range(20):
        v = arbitrary_of(LedgerEntry, 8, random.Random(i))
        want = v.to_xdr()  # the checked-in/loaded engine (or Python path)
        assert cold.pack(prog, v) == want
        assert cold.unpack(prog, want).to_xdr() == want


def _sanitizer_ready():
    """(preload_libs, reason_if_not): the ASan+UBSan leg needs a toolchain
    that links -fsanitize=address,undefined AND names its shared runtimes
    (LD_PRELOAD for the driver subprocess — a sanitized CPython extension
    cannot load into an unsanitized interpreter otherwise)."""
    libs = native.sanitizer_preload_libs()
    if libs is None:
        return None, "toolchain does not expose libasan/libubsan shared runtimes"
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        src = os.path.join(d, "probe.c")
        with open(src, "w") as f:
            f.write("int probe(int x) { return x + 1; }\n")
        ok = native._compile_so(
            src,
            os.path.join(d, "probe.so"),
            ("-fsanitize=address,undefined",),
        )
    if not ok:
        return None, "cc cannot link -fsanitize=address,undefined"
    return libs, None


_SAN_DRIVER = r"""
import hashlib, os, sys, tempfile
import stellar_tpu.native as native

assert native.sanitize_mode() == "address,undefined"

# -- bucketmerge: sha256 differential --------------------------------------
data = b"sanitizer parity \x00\xff" * 700
with tempfile.NamedTemporaryFile(delete=False) as f:
    f.write(data)
try:
    got = native.sha256_file(f.name)
    assert got is not None, "bucketmerge failed to build sanitized"
    assert got == hashlib.sha256(data).digest()
finally:
    os.unlink(f.name)

# -- cxdrpack: pack/unpack + hostile/truncated inputs ----------------------
import random
from stellar_tpu.xdr.arbitrary import arbitrary_of
from stellar_tpu.xdr.base import XdrError, _cspec_of
from stellar_tpu.xdr.entries import LedgerEntry

mod = native.load_cxdrpack()
assert mod is not None, "cxdrpack failed to build sanitized"
defs = []
root = _cspec_of(LedgerEntry._codec, defs, {})
prog = mod.compile(defs, root, XdrError)
for i in range(25):
    v = arbitrary_of(LedgerEntry, 8, random.Random(i))
    octets = mod.pack(prog, v)
    assert mod.unpack(prog, octets).to_xdr() == octets
    # truncated tails must raise, not read out of bounds (ASan's job)
    for cut in (1, 4, len(octets) // 2):
        try:
            mod.unpack(prog, octets[: len(octets) - cut])
        except XdrError:
            pass
    # hostile garbage
    try:
        mod.unpack(prog, b"\xff" * 64)
    except XdrError:
        pass

# -- sighash: stage differential incl. hostile/truncated items -------------
sig_mod = native.load_sighash()
assert sig_mod is not None, "sighash failed to build sanitized"
from stellar_tpu.ops import ref25519 as ref

bl = b"".join(ref.small_order_blacklist())
# item 0 is crafted to PASS the host gate (canonical pk < p, canonical
# s < L, non-blacklisted) so the hashlib differential below always has an
# accepted lane; the rest are hostile randoms
items = [(b"\x42" + b"\x24" * 31, b"known msg",
          b"\x99" * 32 + b"\x01" + b"\x00" * 31)]
rng = random.Random(1234)
for i in range(63):
    pk = bytes(rng.randrange(256) for _ in range(32))
    msg = bytes(rng.randrange(256) for _ in range(rng.randrange(1, 200)))
    sig = bytes(rng.randrange(256) for _ in range(64))
    if i % 5 == 0:
        sig = sig[:32] + b"\xff" * 32  # hostile non-canonical s
    items.append((pk, msg, sig))
out = bytearray(128 * 64)
ok = bytearray(64)
rejects = sig_mod.stage(items, 0, 64, out, ok, bl)
assert 0 <= rejects < 64 and ok[0] == 1
# differential vs hashlib for one accepted lane
for lane, (pk, msg, sig) in enumerate(items):
    if ok[lane]:
        h = int.from_bytes(hashlib.sha512(sig[:32] + pk + msg).digest(),
                           "little") % ref.L
        assert bytes(out[96 * 64 + lane : : 64][:32]) == h.to_bytes(32, "little")
        break
# truncated input rows must raise cleanly, never scribble
try:
    sig_mod.stage([(b"short", b"m", b"s")], 0, 1, bytearray(128), bytearray(1), bl)
except (ValueError, TypeError):
    pass

# -- halfagg: decompress/msm on hostile + structured points ----------------
agg_mod = native.load_halfagg()
assert agg_mod is not None, "halfagg failed to build sanitized"
B_enc = ref.compress(ref.base_point())
pts = [B_enc]
for i in range(40):
    pts.append(bytes(rng.randrange(256) for _ in range(32)))
pts += [b"\x00" * 32, b"\x01" + b"\x00" * 31, b"\xff" * 32]
okf, ext = agg_mod.decompress(b"".join(pts))
assert okf[0] == 1
good = [ext[i * 160 : (i + 1) * 160] for i in range(len(pts)) if okf[i]]
scalars = b"".join(
    (rng.randrange(ref.L)).to_bytes(32, "little") for _ in good
)
out32 = agg_mod.msm_ext(b"".join(good), scalars)
assert len(out32) == 32
# malformed limb blobs must raise, never overflow the accumulators
try:
    agg_mod.msm_ext(b"\xff" * 160, b"\x01" + b"\x00" * 31)
except ValueError:
    pass
else:
    raise SystemExit("msm_ext accepted out-of-bound limbs")
# short/ragged buffers raise cleanly
for bad in (b"\x01" * 31, b"\x01" * 33):
    try:
        agg_mod.msm(bad, b"\x00" * 32)
    except ValueError:
        pass
    else:
        raise SystemExit("msm accepted a ragged buffer")

# -- applycore: batch row encode on ragged/hostile items -------------------
import base64

apl_mod = native.load_applycore()
assert apl_mod is not None, "applycore failed to build sanitized"
rows = [
    (bytes(rng.randrange(256) for _ in range(32)),
     bytes(rng.randrange(256) for _ in range(rng.randrange(0, 400))),
     b"", b"\xff" * 3)
    for _ in range(40)
]
enc = apl_mod.encode_history_rows(rows)
for (t, b, r, m), (ht, bb, br, bm) in zip(rows, enc):
    assert ht == t.hex() and bb == base64.b64encode(b).decode()
    assert br == base64.b64encode(r).decode()
    assert bm == base64.b64encode(m).decode()
# non-bytes / short tuples must raise cleanly, never scribble
for bad in ([(b"x",)], [("s", b"", b"", b"")], "nope"):
    try:
        apl_mod.encode_history_rows(bad)
    except (TypeError, ValueError):
        pass
    else:
        raise SystemExit("applycore accepted a malformed item")
# the fee pass's rows: (index, txid, changes) -> (hex, seq, index, base64)
fee_items = [(i + 1, t, b) for i, (t, b, _r, _m) in enumerate(rows)]
assert apl_mod.encode_fee_rows(7, fee_items) == [
    (t.hex(), 7, i, base64.b64encode(b).decode()) for i, t, b in fee_items
]
assert apl_mod.encode_fee_rows(7, []) == []
for bad in ([(1, b"x")], [(b"x", b"y")], [(1, "s", b"")], [(1, b"x", b"y", b"z")], "nope"):
    try:
        apl_mod.encode_fee_rows(7, bad)
    except (TypeError, ValueError):
        pass
    else:
        raise SystemExit("applycore accepted a malformed fee item")

# -- sodium pool leg (skipped silently when libsodium is absent) -----------
try:
    from stellar_tpu.crypto import sodium

    fn = sodium.verify_fn_addr()
except Exception:
    fn = None
if fn is not None and hasattr(sig_mod, "sodium_verify"):
    okb = bytearray(len(items))
    sig_mod.sodium_verify(fn, items, okb)
    assert set(okb) <= {0, 1}

print("SAN_OK")
"""


@pytest.mark.slow
def test_sanitized_build_differentials():
    """ASan+UBSan leg: rebuild all five extensions with
    -fsanitize=address,undefined (the STELLAR_TPU_SANITIZE plumb-through,
    separate .san.so artifacts) and run the hostile/truncated-input
    differentials inside a driver subprocess with the sanitizer runtimes
    preloaded.  Any out-of-bounds read/UB the normal suite can't see
    aborts the driver and fails here."""
    libs, reason = _sanitizer_ready()
    if libs is None:
        pytest.skip(reason)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update(
        STELLAR_TPU_SANITIZE="address,undefined",
        LD_PRELOAD=":".join(libs),
        # leak accounting is meaningless for a short-lived driver and noisy
        # under CPython's arena allocator; hard-abort on real errors
        ASAN_OPTIONS="detect_leaks=0,abort_on_error=1",
        UBSAN_OPTIONS="halt_on_error=1,print_stacktrace=1",
        PYTHONPATH=repo,
    )
    import subprocess
    import sys

    p = subprocess.run(
        [sys.executable, "-c", _SAN_DRIVER],
        capture_output=True,
        text=True,
        timeout=600,
        env=env,
        cwd=repo,
    )
    assert p.returncode == 0, (
        f"sanitized driver failed rc={p.returncode}\n--- stdout ---\n"
        f"{p.stdout[-4000:]}\n--- stderr ---\n{p.stderr[-4000:]}"
    )
    assert "SAN_OK" in p.stdout


def test_halfagg_cold_build_msm_differential(cold_dir):
    cold = native._load_extension(
        "_halfagg", str(cold_dir / "halfagg.c"),
        str(cold_dir / "_halfagg.so"),
    )
    assert cold is not None, "halfagg.c failed to compile from source"
    import random

    from stellar_tpu.ops import ref25519 as ref

    rng = random.Random(5)
    B = ref.base_point()
    pts, scs, expect = [], [], ref.IDENT
    for _ in range(9):
        pt = ref.scalar_mult(rng.randrange(1, ref.L), B)
        s = rng.randrange(ref.L)
        pts.append(ref.compress(pt))
        scs.append(s.to_bytes(32, "little"))
        expect = ref.point_add(expect, ref.scalar_mult(s, pt))
    out = cold.msm(b"".join(pts), b"".join(scs))
    assert out == ref.compress(expect)
    warm = native.load_halfagg()
    assert warm.msm(b"".join(pts), b"".join(scs)) == out


def test_sighash_cold_build_stage_differential(cold_dir):
    cold = native._load_extension(
        "_sighash", str(cold_dir / "sighash.c"),
        str(cold_dir / "_sighash.so"), ("-pthread",),
    )
    assert cold is not None, "sighash.c failed to compile from source"
    warm = native.load_sighash()
    from stellar_tpu.crypto import SecretKey
    from stellar_tpu.ops import ref25519 as ref

    bl = b"".join(ref.small_order_blacklist())
    items = []
    for i in range(64):
        sk = SecretKey.pseudo_random_for_testing(i)
        msg = b"cold %d" % i
        sig = sk.sign(msg) if i % 4 else b"\x00" * 64
        items.append((sk.public_raw, msg, sig))
    pc = np.zeros((128, 64), np.uint8)
    kc = np.zeros(64, np.uint8)
    pw = np.zeros((128, 64), np.uint8)
    kw = np.zeros(64, np.uint8)
    rc = cold.stage(items, 0, 64, pc, kc, bl)
    rw = warm.stage(items, 0, 64, pw, kw, bl)
    assert rc == rw and (kc == kw).all() and (pc == pw).all()
    # and against hashlib directly for one fast-path item
    p, m, s = items[1]
    h = (
        int.from_bytes(hashlib.sha512(s[:32] + p + m).digest(), "little")
        % ref.L
    )
    assert bytes(pc[96:128, 1]) == h.to_bytes(32, "little")


def test_applycore_cold_build_encode_differential(cold_dir):
    cold = native._load_extension(
        "_applycore", str(cold_dir / "applycore.c"),
        str(cold_dir / "_applycore.so"),
    )
    assert cold is not None, "applycore.c failed to compile from source"
    import base64
    import random

    rng = random.Random(17)
    items = [
        (
            bytes(rng.randrange(256) for _ in range(32)),
            bytes(rng.randrange(256) for _ in range(rng.randrange(0, 300))),
            bytes(rng.randrange(256) for _ in range(rng.randrange(1, 40))),
            bytes(rng.randrange(256) for _ in range(rng.randrange(1, 120))),
        )
        for _ in range(50)
    ]
    got = cold.encode_history_rows(items)
    want = [
        (
            t.hex(),
            base64.b64encode(b).decode(),
            base64.b64encode(r).decode(),
            base64.b64encode(m).decode(),
        )
        for t, b, r, m in items
    ]
    assert got == want
    warm = native.load_applycore()
    assert warm.encode_history_rows(items) == want
    fee_items = [(i + 1, t, b) for i, (t, b, _r, _m) in enumerate(items)]
    fee_want = [(h, 3, i, b) for i, (h, b, _r, _m) in enumerate(want, start=1)]
    assert cold.encode_fee_rows(3, fee_items) == fee_want
    assert warm.encode_fee_rows(3, fee_items) == fee_want
