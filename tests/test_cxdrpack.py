"""Differential tests: the C pack interpreter (native/cxdrpack.c) vs the
pure-Python codec (xdr/base.py) — byte-for-byte equality over every
registered XDR type with fuzzed values, plus the failure contract (both
paths raise XdrError for the same malformed inputs).

Every hash in the system is a SHA-256 over these octets, so this is a
consensus-critical equivalence (same bar as tests/test_native_merge.py for
the C merge engine).
"""

import random

import pytest

import stellar_tpu.xdr as X
from stellar_tpu.xdr import arbitrary
from stellar_tpu.xdr.base import XdrError, codec_of, _cxdr

cxdr = _cxdr()
pytestmark = pytest.mark.skipif(
    cxdr is None, reason="no C toolchain for cxdrpack"
)


def _registered_types():
    """Every xstruct/xunion class exposed by the xdr package modules."""
    import stellar_tpu.xdr.entries as entries
    import stellar_tpu.xdr.ledger as ledger
    import stellar_tpu.xdr.overlay as overlay
    import stellar_tpu.xdr.scp as scp
    import stellar_tpu.xdr.txs as txs
    import stellar_tpu.xdr.xtypes as xtypes

    out = []
    for mod in (xtypes, entries, txs, ledger, scp, overlay):
        for name in dir(mod):
            cls = getattr(mod, name)
            if isinstance(cls, type) and hasattr(cls, "_codec"):
                out.append(cls)
    # dedup by codec identity (re-exports)
    seen, uniq = set(), []
    for cls in out:
        if id(cls._codec) not in seen:
            seen.add(id(cls._codec))
            uniq.append(cls)
    return uniq


TYPES = _registered_types()


def _py_pack(codec, val) -> bytes:
    out = bytearray()
    codec.pack_into(val, out)
    return bytes(out)


def test_catalog_is_meaningful():
    names = {c.__name__ for c in TYPES}
    assert {
        "TransactionEnvelope", "LedgerEntry", "TransactionMeta",
        "SCPEnvelope", "StellarMessage", "LedgerHeader", "SCPQuorumSet",
    } <= names
    assert len(TYPES) > 40


def _seed(cls) -> int:
    """Stable across processes (hash() is PYTHONHASHSEED-randomized —
    a failing fuzz case must reproduce)."""
    import zlib

    return zlib.crc32(cls.__name__.encode())


@pytest.mark.parametrize("cls", TYPES, ids=lambda c: c.__name__)
def test_c_pack_matches_python_pack(cls):
    rng = random.Random(_seed(cls))
    codec = codec_of(cls)
    for i in range(25):
        val = arbitrary.arbitrary(codec, size=8, rng=rng)
        expect = _py_pack(codec, val)
        got = codec.pack(val)
        if codec._cprog is False:
            pytest.skip(f"{cls.__name__}: C compilation unsupported")
        assert got == expect, f"{cls.__name__} iteration {i}"


def test_all_catalog_types_compile_to_c():
    """No silent fallback: every registered type must take the C path (a
    new codec kind that can't compile should be a conscious decision)."""
    for cls in TYPES:
        codec = codec_of(cls)
        codec.pack(arbitrary.arbitrary(codec, size=4, rng=random.Random(1)))
        assert codec._cprog is not False, cls.__name__


@pytest.mark.parametrize("cls", TYPES, ids=lambda c: c.__name__)
def test_c_copy_matches_python_copy(cls):
    """xdr_copy's C path: the copy packs to identical bytes, and mutable
    values are truly independent of the original."""
    from stellar_tpu.xdr.base import xdr_copy

    rng = random.Random(_seed(cls) ^ 1)
    codec = codec_of(cls)
    for _ in range(10):
        val = arbitrary.arbitrary(codec, size=8, rng=rng)
        dup = xdr_copy(val)
        assert _py_pack(codec, dup) == _py_pack(codec, val)
        if codec.immutable:
            assert dup is val  # declared value-semantics: shared
        else:
            py_dup = codec.copy(val)
            assert _py_pack(codec, py_dup) == _py_pack(codec, dup)


def test_c_copy_is_independent():
    from stellar_tpu.xdr.base import xdr_copy
    from stellar_tpu.xdr.entries import AccountEntry

    val = arbitrary.arbitrary_of(AccountEntry, size=6,
                                 rng=random.Random(11))
    dup = xdr_copy(val)
    assert dup is not val
    dup.balance = (val.balance or 0) + 7
    assert val.balance != dup.balance
    dup.signers.append("sentinel")
    assert len(val.signers) == len(dup.signers) - 1


def _py_unpack(codec, data):
    val, off = codec.unpack_from(data, 0)
    assert off == len(data)
    return val


@pytest.mark.parametrize("cls", TYPES, ids=lambda c: c.__name__)
def test_c_unpack_matches_python_unpack(cls):
    """from_xdr's C path: decoded objects equal the Python decoder's and
    re-pack to the identical octets."""
    rng = random.Random(_seed(cls) ^ 2)
    codec = codec_of(cls)
    for _ in range(15):
        val = arbitrary.arbitrary(codec, size=8, rng=rng)
        data = _py_pack(codec, val)
        got = codec.unpack(data)  # C path
        want = _py_unpack(codec, data)
        assert got == want, cls.__name__
        assert _py_pack(codec, got) == data


class TestUnpackFailureContract:
    def _codec(self):
        from stellar_tpu.xdr.entries import AccountEntry

        return codec_of(AccountEntry)

    def _payload(self):
        c = self._codec()
        val = arbitrary.arbitrary(
            c, size=4, rng=random.Random(21)
        )
        return c, _py_pack(c, val)

    def test_truncated(self):
        c, data = self._payload()
        for cut in (1, 4, len(data) // 2, len(data) - 1):
            with pytest.raises(XdrError):
                c.unpack(data[:cut])

    def test_trailing_bytes(self):
        c, data = self._payload()
        with pytest.raises(XdrError, match="trailing"):
            c.unpack(data + b"\x00\x00\x00\x00")

    def test_nonzero_padding(self):
        from stellar_tpu.xdr.base import var_opaque

        blob = var_opaque(64).pack(b"abc")  # 3 bytes + 1 pad byte
        bad = blob[:-1] + b"\x07"
        vo = var_opaque(64)
        vo._cprog = None  # standalone codec: force fresh compile
        with pytest.raises(XdrError):
            vo.unpack(bad)
        with pytest.raises(XdrError):
            vo.unpack_from(bad, 0)

    def test_hostile_vararray_count_is_short_buffer(self):
        """count=0xFFFFFFFF on an unbounded vararray must raise XdrError
        (short buffer), never attempt a 34 GB list preallocation."""
        from stellar_tpu.xdr.base import uint32, var_array

        va = var_array(uint32)
        va._cprog = None
        with pytest.raises(XdrError):
            va.unpack(b"\xff\xff\xff\xff")
        from stellar_tpu.xdr.scp import SCPQuorumSet

        # wire-reachable shape: quorum set claiming 2^32-1 validators
        blob = b"\x00\x00\x00\x01" + b"\xff\xff\xff\xff"
        with pytest.raises(XdrError):
            codec_of(SCPQuorumSet).unpack(blob)

    def test_bad_enum_on_wire(self):
        from stellar_tpu.xdr.entries import AssetType

        a = X.Asset.native()
        data = codec_of(a).pack(a)
        bad = b"\x00\x00\x00\x63" + data[4:]  # discriminant 99
        with pytest.raises(XdrError):
            codec_of(a).unpack(bad)

    def test_unpack_recursion_depth_bounded(self):
        """Hand-crafted wire bytes of a 12-deep quorum set: both decoders
        must hit the depth guard, not RecursionError."""
        import struct as _struct

        from stellar_tpu.xdr.scp import SCPQuorumSet

        blob = _struct.pack(">III", 1, 0, 0)  # innermost: no inner sets
        for _ in range(12):
            blob = _struct.pack(">III", 1, 0, 1) + blob
        with pytest.raises(XdrError, match="recursion"):
            codec_of(SCPQuorumSet).unpack(blob)  # C path
        with pytest.raises(XdrError, match="recursion"):
            codec_of(SCPQuorumSet).unpack_from(blob, 0)  # python path


class TestCompileGuards:
    """Compile-side degradation: shapes the C interpreter can't model (or
    refuses) must fall back to the Python codec, never raise or diverge
    (advisor r04 findings #2 and #3)."""

    def test_short_element_vararray_stays_python(self):
        """opaque[0] / array[T,0] elements have minimum wire size 0; the C
        unpacker's count guard assumes >= 4 bytes/element, so these codecs
        must be rejected at compile time and served by the Python path."""
        from stellar_tpu.xdr.base import array, opaque, uint32, var_array

        for elem, vals in (
            (opaque(0), [b"", b"", b""]),
            (array(uint32, 0), [[], []]),
        ):
            va = var_array(elem, 8)
            data = va.pack(vals)
            assert va._cprog is False, "C path must refuse short elements"
            assert va.unpack(data) == vals

    def test_min_wire_size_model(self):
        from stellar_tpu.xdr.base import (
            _min_wire_size, array, codec_of, opaque, option, uint32, uint64,
            var_opaque,
        )
        from stellar_tpu.xdr.scp import SCPQuorumSet

        assert _min_wire_size(uint32) == 4
        assert _min_wire_size(uint64) == 8
        assert _min_wire_size(opaque(0)) == 0
        assert _min_wire_size(opaque(3)) == 4  # padded
        assert _min_wire_size(array(uint32, 0)) == 0
        assert _min_wire_size(var_opaque(64)) == 4  # count alone
        assert _min_wire_size(option(opaque(0))) == 4
        # recursive type: terminates, and is >= 4 (threshold + two counts)
        assert _min_wire_size(codec_of(SCPQuorumSet)) >= 4

    def test_compile_valueerror_degrades_to_python(self):
        """A codec tree with more depth guards than the C interpreter's
        MAX_DEPTH_SLOTS: mod.compile raises ValueError, which must latch
        _cprog=False and degrade to the Python path — not escape pack()."""
        from stellar_tpu.xdr.base import DepthLimited, uint32

        c = uint32
        for _ in range(17):  # cxdrpack.c MAX_DEPTH_SLOTS == 16
            c = DepthLimited(c, max_depth=32)
        data = c.pack(7)
        assert c._cprog is False
        assert c.unpack(data) == 7
        assert c.pack(9) == b"\x00\x00\x00\x09"  # stays on Python path


class TestFailureContract:
    def test_bad_enum_value(self):
        env = X.TransactionEnvelope(
            tx=None, signatures=[]
        )
        # malformed: tx must be a Transaction; C must raise XdrError too
        with pytest.raises(XdrError):
            codec_of(env).pack(env)

    def test_short_opaque(self):
        pk = X.PublicKey.from_ed25519(b"\x01" * 31)  # wrong length
        with pytest.raises(XdrError):
            codec_of(pk).pack(pk)

    def test_void_arm_with_value(self):
        a = X.Asset(X.AssetType.ASSET_TYPE_NATIVE, 123)
        with pytest.raises(XdrError):
            codec_of(a).pack(a)

    def test_bad_union_discriminant(self):
        a = X.Asset(9999, None)
        with pytest.raises(XdrError):
            codec_of(a).pack(a)

    def test_unencodable_string_raises_xdr_error(self):
        """A lone surrogate is a constructible str that cannot encode to
        UTF-8: both paths must raise XdrError, not UnicodeEncodeError."""
        from stellar_tpu.xdr.entries import AccountEntry

        val = arbitrary.arbitrary_of(AccountEntry, size=4,
                                     rng=random.Random(7))
        val.homeDomain = "\ud800"
        codec = codec_of(val)
        with pytest.raises(XdrError):
            codec.pack(val)  # C path
        out = bytearray()
        with pytest.raises(XdrError):
            codec.pack_into(val, out)  # python path

    def test_string_too_long(self):
        from stellar_tpu.xdr.entries import AccountEntry

        rng = random.Random(3)
        val = arbitrary.arbitrary_of(AccountEntry, size=4, rng=rng)
        val.homeDomain = "x" * 33
        with pytest.raises(XdrError):
            codec_of(val).pack(val)

    def test_recursion_depth_bounded(self):
        from stellar_tpu.xdr.scp import SCPQuorumSet

        q = SCPQuorumSet(1, [], [])
        for _ in range(10):  # deeper than the depth-8 guard
            q = SCPQuorumSet(1, [], [q])
        with pytest.raises(XdrError):
            codec_of(q).pack(q)
        # python path agrees
        out = bytearray()
        with pytest.raises(XdrError):
            codec_of(q).pack_into(q, out)

    def test_uint64_negative(self):
        h = X.Price(1, 1)
        c = codec_of(h)
        bad = X.Price(-1, 1)  # int32 arm accepts -1; use uint64 type instead
        from stellar_tpu.xdr.entries import AccountEntry

        val = arbitrary.arbitrary_of(AccountEntry, size=4,
                                     rng=random.Random(4))
        val.balance = -5  # int64 ok; seqNum uint64? check via flags
        val.flags = -1  # uint32 field
        with pytest.raises(XdrError):
            codec_of(val).pack(val)


# -- hot-field accessors (getfield/setfield, round 7) -----------------------


def _scalar_paths_of(codec, val):
    """Every scalar field path in a decoded value with its oracle value —
    the shared walker (xdr/base.py iter_scalar_field_paths), filtered to
    non-root paths (the root itself isn't a field)."""
    from stellar_tpu.xdr.base import iter_scalar_field_paths

    for path, _leaf, v in iter_scalar_field_paths(codec, val):
        if path:
            yield path, v


@pytest.mark.parametrize("cls", TYPES, ids=lambda c: c.__name__)
def test_getfield_matches_attribute_walk(cls):
    """Fuzzed differential: for every scalar path of every registered
    type, the C byte-walker answers exactly what the decoded object
    holds."""
    from stellar_tpu.xdr.base import xdr_getfield

    rng = random.Random(_seed(cls) ^ 3)
    codec = codec_of(cls)
    checked = 0
    for _ in range(8):
        val = arbitrary.arbitrary(codec, size=6, rng=rng)
        try:
            data = _py_pack(codec, val)
        except XdrError:
            continue
        for path, want in _scalar_paths_of(codec, val):
            got = xdr_getfield(codec, data, path)
            assert got == want, (cls.__name__, path)
            checked += 1
    if checked == 0:
        pytest.skip(f"{cls.__name__}: no scalar paths in fuzzed values")


def test_getfield_absent_option_is_none():
    from stellar_tpu.xdr.base import xdr_getfield
    from stellar_tpu.xdr.entries import AccountEntry

    val = arbitrary.arbitrary_of(AccountEntry, size=4, rng=random.Random(9))
    val.inflationDest = None
    data = _py_pack(codec_of(val), val)
    assert xdr_getfield(AccountEntry, data, "inflationDest") is None


def test_getfield_terminal_union_discriminant():
    """A path TERMINATING at a union reads its discriminant as a plain
    int (ISSUE r15: the herder's post-verify statement-type hot read) —
    C walker and decoded-object oracle agree for every statement type,
    truncation raises, and setfield refuses the discriminant."""
    from stellar_tpu.xdr.base import XdrError, xdr_getfield, xdr_setfield
    from stellar_tpu.xdr.scp import (
        SCPBallot,
        SCPEnvelope,
        SCPNomination,
        SCPStatement,
        SCPStatementConfirm,
        SCPStatementPledges,
        SCPStatementType,
    )
    from stellar_tpu.xdr.xtypes import PublicKey

    def envelope_for(t):
        if t == SCPStatementType.SCP_ST_NOMINATE:
            pledges = SCPStatementPledges(
                t, SCPNomination(b"\x02" * 32, [b"vote"], [])
            )
        else:
            pledges = SCPStatementPledges(
                t,
                SCPStatementConfirm(
                    b"\x11" * 32, 1, SCPBallot(1, b"v"), 1
                ),
            )
        return SCPEnvelope(
            statement=SCPStatement(
                nodeID=PublicKey.from_ed25519(b"\x01" * 32),
                slotIndex=42,
                pledges=pledges,
            ),
            signature=b"\x03" * 64,
        )

    for t in (
        SCPStatementType.SCP_ST_CONFIRM,
        SCPStatementType.SCP_ST_NOMINATE,
    ):
        env = envelope_for(t)
        raw = env.to_xdr()
        got = xdr_getfield(SCPEnvelope, raw, ("statement", "pledges"))
        assert got == int(env.statement.pledges.type) == int(t)
        # nodeID is a union too (key type); and the scalar neighbor reads
        assert xdr_getfield(SCPEnvelope, raw, ("statement", "nodeID")) == 0
        assert xdr_getfield(SCPEnvelope, raw, "statement.slotIndex") == 42
        with pytest.raises(XdrError):
            xdr_getfield(SCPEnvelope, raw[:40], ("statement", "pledges"))
        with pytest.raises(XdrError, match="discriminant"):
            xdr_setfield(SCPEnvelope, raw, ("statement", "pledges"), 1)


def test_getfield_terminal_union_python_walk_parity():
    """The Python fallback resolution marks terminal-union paths and
    would return int(obj.type) — same value the C walker reads."""
    from stellar_tpu.xdr import base as B
    from stellar_tpu.xdr.base import codec_of
    from stellar_tpu.xdr.scp import (
        SCPEnvelope,
        SCPNomination,
        SCPStatement,
        SCPStatementPledges,
        SCPStatementType,
    )
    from stellar_tpu.xdr.xtypes import PublicKey

    env = SCPEnvelope(
        statement=SCPStatement(
            nodeID=PublicKey.from_ed25519(b"\x01" * 32),
            slotIndex=7,
            pledges=SCPStatementPledges(
                SCPStatementType.SCP_ST_NOMINATE,
                SCPNomination(b"\x02" * 32, [], []),
            ),
        ),
        signature=b"\x03" * 64,
    )
    codec = codec_of(SCPEnvelope)
    steps, norm, union_terminal = B._field_path_of(
        codec, ("statement", "pledges")
    )
    assert union_terminal
    obj = B._py_walk(codec.unpack(env.to_xdr()), norm)
    assert int(obj.type) == int(SCPStatementType.SCP_ST_NOMINATE)
    # scalar paths stay non-union
    _, _, ut = B._field_path_of(codec, "statement.slotIndex")
    assert not ut


def test_setfield_differential_vs_repack():
    """Patching a fixed-width scalar in the bytes must equal setattr +
    full repack, for every fixed-width path of a fuzzed LedgerEntry."""
    from stellar_tpu.xdr import base as B
    from stellar_tpu.xdr.base import xdr_setfield
    from stellar_tpu.xdr.entries import LedgerEntry

    rng = random.Random(31)
    codec = codec_of(LedgerEntry)
    for _ in range(10):
        val = arbitrary.arbitrary(codec, size=6, rng=rng)
        data = _py_pack(codec, val)
        for path, _old in _scalar_paths_of(codec, val):
            steps, norm, _union = B._field_path_of(codec, path)
            _, leaf = B._resolve_field_path(codec, norm)
            if isinstance(leaf, B._UInt32):
                new = rng.getrandbits(32)
            elif isinstance(leaf, B._Int64):
                new = rng.getrandbits(62)
            elif isinstance(leaf, B._UInt64):
                new = rng.getrandbits(64)
            elif isinstance(leaf, B._Int32):
                new = rng.getrandbits(30)
            elif isinstance(leaf, B._Bool):
                new = True
            elif isinstance(leaf, B._Enum):
                new = rng.choice(list(leaf.enum_cls))
            elif isinstance(leaf, B._Opaque):
                new = bytes(rng.getrandbits(8) for _ in range(leaf.n))
            else:
                continue  # var-width (string/varopaque): not patchable
            got = xdr_setfield(codec, data, path, new)
            # oracle: decode, set via the same walk, repack
            obj = codec.unpack(data)
            parent = B._py_walk(obj, norm[:-1])
            last = norm[-1]
            if isinstance(last, str):
                object.__setattr__(parent, last, new)
            elif isinstance(parent, list):
                parent[last] = new
            else:
                object.__setattr__(parent, "value", new)
            assert got == _py_pack(codec, obj), path


class TestFieldAccessHostilePaths:
    def _payload(self):
        from stellar_tpu.xdr.entries import LedgerEntry

        codec = codec_of(LedgerEntry)
        val = arbitrary.arbitrary(codec, size=5, rng=random.Random(41))
        return codec, _py_pack(codec, val), val

    def test_truncated_buffers(self):
        from stellar_tpu.xdr.base import xdr_getfield

        codec, data, val = self._payload()
        path = ("data", int(val.data.type), "flags")
        oracle = xdr_getfield(codec, data, path)
        for cut in range(0, len(data), 3):
            # every truncation either raises a clean XdrError, or the walk
            # legitimately completed before the cut — in which case the
            # answer must be THE true value (a bounds bug returning bytes
            # read past the cut would produce garbage and fail here)
            try:
                got = xdr_getfield(codec, data[:cut], path)
            except XdrError:
                continue
            assert got == oracle, f"cut {cut}: wrong value from truncation"

    def test_union_arm_mismatch(self):
        from stellar_tpu.xdr.base import xdr_getfield
        from stellar_tpu.xdr.entries import LedgerEntryType

        codec, data, val = self._payload()
        wrong = (
            LedgerEntryType.TRUSTLINE
            if val.data.type != LedgerEntryType.TRUSTLINE
            else LedgerEntryType.OFFER
        )
        field = "balance" if wrong == LedgerEntryType.TRUSTLINE else "amount"
        with pytest.raises(XdrError, match="arm mismatch"):
            xdr_getfield(codec, data, ("data", int(wrong), field))

    def test_void_arm_and_unknown_field_fail_at_resolve(self):
        from stellar_tpu.xdr.base import xdr_getfield
        import stellar_tpu.xdr as X

        a = X.Asset.native()
        data = codec_of(a).pack(a)
        with pytest.raises(KeyError):  # native arm is void
            xdr_getfield(codec_of(a), data, (int(X.AssetType.ASSET_TYPE_NATIVE),))
        codec, payload, _ = self._payload()
        with pytest.raises(KeyError):
            xdr_getfield(codec, payload, "noSuchField")

    def test_path_into_scalar_rejected(self):
        from stellar_tpu.xdr.base import xdr_getfield

        codec, data, _ = self._payload()
        with pytest.raises(TypeError):
            xdr_getfield(codec, data, "lastModifiedLedgerSeq.x")

    def test_array_index_out_of_range(self):
        from stellar_tpu.xdr.base import xdr_getfield
        from stellar_tpu.xdr.entries import (
            AccountEntry, LedgerEntry, LedgerEntryData, LedgerEntryType,
            PublicKey, Signer,
        )

        ae = arbitrary.arbitrary_of(AccountEntry, size=3,
                                    rng=random.Random(5))
        ae.signers = [Signer(PublicKey.from_ed25519(b"\x01" * 32), 1)]
        le = LedgerEntry(0, LedgerEntryData(LedgerEntryType.ACCOUNT, ae), 0)
        data = _py_pack(codec_of(le), le)
        path = ("data", int(LedgerEntryType.ACCOUNT), "signers", 5, "weight")
        with pytest.raises(XdrError, match="out of range"):
            xdr_getfield(codec_of(le), data, path)

    def test_setfield_rejects_varwidth_and_bad_values(self):
        from stellar_tpu.xdr.base import xdr_setfield
        from stellar_tpu.xdr.entries import LedgerEntryType

        codec, data, val = self._payload()
        arm = int(val.data.type)
        if val.data.type == LedgerEntryType.ACCOUNT:
            with pytest.raises(XdrError, match="fixed-width"):
                xdr_setfield(codec, data, ("data", arm, "homeDomain"), "x")
            with pytest.raises(XdrError):  # uint32 out of range
                xdr_setfield(codec, data, ("data", arm, "flags"), -1)
            with pytest.raises(XdrError):  # opaque[4] wrong length
                xdr_setfield(codec, data, ("data", arm, "thresholds"), b"xy")
        with pytest.raises(XdrError):  # truncated buffer
            xdr_setfield(codec, data[:3], ("lastModifiedLedgerSeq",), 1)

    def test_setfield_patch_is_surgical(self):
        """Only the patched field differs; everything else is bitwise
        untouched (the whole point: no repack of the rest)."""
        from stellar_tpu.xdr.base import xdr_setfield

        codec, data, val = self._payload()
        out = xdr_setfield(codec, data, ("lastModifiedLedgerSeq",), 0x0A0B0C0D)
        assert len(out) == len(data)
        diff = [i for i, (x, y) in enumerate(zip(data, out)) if x != y]
        assert diff and max(diff) - min(diff) < 4, "patch must stay in-field"
        assert codec.unpack(out).lastModifiedLedgerSeq == 0x0A0B0C0D


# -- pack_many batch encoder (round 9, bucket add_batch plane) --------------


class TestPackMany:
    """pack_many(values, cls, frames=) must emit exactly the octets of the
    per-value pack loop (optionally with RFC 5531 record marks — the
    XDROutputFileStream framing the bucket files use), share pack's
    XdrError failure contract on a malformed element, and stay available
    through the Python fallback on extension-less hosts."""

    def _entries(self, n=40, seed=909):
        from stellar_tpu.xdr.entries import LedgerEntry

        rng = random.Random(seed)
        codec = codec_of(LedgerEntry)
        return codec, [
            arbitrary.arbitrary(codec, size=6, rng=rng) for _ in range(n)
        ]

    def test_differential_vs_per_entry_to_xdr(self):
        from stellar_tpu.xdr.base import pack_many

        codec, vals = self._entries()
        assert pack_many(vals, codec) == b"".join(
            v.to_xdr() for v in vals
        )

    def test_framed_differential_vs_xdrstream(self, tmp_path):
        """frames=True is byte-identical to what XDROutputFileStream
        writes record-by-record (the bucket-file wire format)."""
        from stellar_tpu.util.xdrstream import XDROutputFileStream
        from stellar_tpu.xdr.base import pack_many

        codec, vals = self._entries(seed=910)
        path = str(tmp_path / "stream.xdr")
        with XDROutputFileStream(path) as s:
            for v in vals:
                s.write_one(v)
        with open(path, "rb") as f:
            expect = f.read()
        assert pack_many(vals, codec, frames=True) == expect

    def test_accepts_class_iterable_and_empty(self):
        from stellar_tpu.xdr.entries import LedgerEntry
        from stellar_tpu.xdr.base import pack_many

        codec, vals = self._entries(n=5, seed=911)
        joined = b"".join(v.to_xdr() for v in vals)
        assert pack_many(vals, LedgerEntry) == joined  # class, not codec
        assert pack_many(iter(vals), codec) == joined  # generator input
        assert pack_many([], codec) == b""
        assert pack_many([], codec, frames=True) == b""

    def test_bucketentry_batch_matches_loop(self):
        """The actual add_batch payload type: mixed live/dead records."""
        from stellar_tpu.xdr.ledger import (
            BucketEntry, BucketEntryType, LedgerKey,
        )
        from stellar_tpu.ledger.entryframe import ledger_key_of
        from stellar_tpu.xdr.base import pack_many

        codec, vals = self._entries(n=24, seed=912)
        batch = []
        for i, e in enumerate(vals):
            if i % 3 == 0:
                batch.append(
                    BucketEntry(BucketEntryType.DEADENTRY, ledger_key_of(e))
                )
            else:
                batch.append(BucketEntry(BucketEntryType.LIVEENTRY, e))
        got = pack_many(batch, BucketEntry, frames=True)
        expect = bytearray()
        import struct as _struct

        for b in batch:
            body = b.to_xdr()
            expect += _struct.pack(">I", len(body) | 0x80000000) + body
        assert got == bytes(expect)

    @pytest.mark.parametrize("poison", [
        lambda v: setattr(v, "lastModifiedLedgerSeq", -1),  # uint32 < 0
        lambda v: setattr(v, "data", None),                 # truncated entry
        lambda v: setattr(
            v, "data", X.Asset(9999, None)
        ),                                                  # foreign type
    ], ids=["negative-uint32", "missing-union", "foreign-struct"])
    def test_hostile_element_raises_and_discards_batch(self, poison):
        """One malformed element anywhere in the batch: XdrError, nothing
        returned (the partial buffer must not leak out), and the same
        batch without the poisoned element still packs."""
        from stellar_tpu.xdr.base import pack_many

        codec, vals = self._entries(n=12, seed=913)
        poison(vals[7])
        for frames in (False, True):
            with pytest.raises(XdrError):
                pack_many(vals, codec, frames=frames)
        rest = vals[:7] + vals[8:]
        assert pack_many(rest, codec) == b"".join(
            v.to_xdr() for v in rest
        )

    def test_python_fallback_matches_c(self, monkeypatch):
        """A codec the C side does not model (``_cprog is False``) drops
        pack_many to its per-value Python loop — same octets, framed and
        unframed."""
        import stellar_tpu.xdr.base as B

        codec, vals = self._entries(n=10, seed=914)
        want_plain = B.pack_many(vals, codec)
        want_framed = B.pack_many(vals, codec, frames=True)
        monkeypatch.setattr(codec, "_cprog", False)
        assert B.pack_many(vals, codec) == want_plain
        assert B.pack_many(vals, codec, frames=True) == want_framed
