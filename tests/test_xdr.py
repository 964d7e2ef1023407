"""XDR runtime + protocol type tests.

Shaped like the reference's xdrpp round-trip usage and golden encodings
hand-derived from RFC 4506 (every struct/union below is checked against
bytes computed independently from the spec, not from our own packer).
"""

import pytest
from hypothesis import given, settings, strategies as st

import stellar_tpu.xdr as X
from stellar_tpu.xdr.base import XdrError, uint32, int32, uint64, int64, var_opaque


PK = X.PublicKey.from_ed25519(bytes(range(32)))


class TestPrimitives:
    def test_uint32_golden(self):
        assert uint32.pack(0x01020304) == b"\x01\x02\x03\x04"

    def test_int32_golden(self):
        assert int32.pack(-1) == b"\xff\xff\xff\xff"

    def test_uint64_golden(self):
        assert uint64.pack(0x0102030405060708) == bytes(range(1, 9))

    def test_int64_golden(self):
        assert int64.pack(-2) == b"\xff" * 7 + b"\xfe"

    def test_var_opaque_padding(self):
        # length prefix + data + zero pad to 4
        assert var_opaque().pack(b"abcde") == b"\x00\x00\x00\x05abcde\x00\x00\x00"

    def test_var_opaque_max_enforced(self):
        with pytest.raises(XdrError):
            var_opaque(4).pack(b"abcde")

    def test_nonzero_padding_rejected(self):
        with pytest.raises(XdrError):
            var_opaque().unpack(b"\x00\x00\x00\x01a\x00\x00\x01")

    def test_uint32_range(self):
        with pytest.raises(XdrError):
            uint32.pack(-1)
        with pytest.raises(XdrError):
            uint32.pack(1 << 32)

    def test_trailing_bytes_rejected(self):
        with pytest.raises(XdrError):
            uint32.unpack(b"\x00" * 8)


class TestGoldenEncodings:
    """Encodings computed by hand from RFC 4506 + the .x definitions."""

    def test_scp_ballot(self):
        # counter=5 | len=5 "hello" + 3 pad
        assert (
            X.SCPBallot(5, b"hello").to_xdr().hex()
            == "000000050000000568656c6c6f000000"
        )

    def test_public_key(self):
        # discriminant KEY_TYPE_ED25519=0 | 32 raw bytes
        assert PK.to_xdr() == b"\x00\x00\x00\x00" + bytes(range(32))

    def test_asset_native(self):
        assert X.Asset.native().to_xdr() == b"\x00\x00\x00\x00"

    def test_asset_alphanum4(self):
        a = X.Asset.alphanum4(b"USD", PK)
        # type=1 | code "USD\0" | issuer pk
        assert a.to_xdr() == b"\x00\x00\x00\x01USD\x00" + PK.to_xdr()

    def test_price(self):
        assert X.Price(3, 2).to_xdr() == b"\x00\x00\x00\x03\x00\x00\x00\x02"

    def test_memo_none(self):
        assert X.Memo.none().to_xdr() == b"\x00\x00\x00\x00"

    def test_memo_text(self):
        assert (
            X.Memo(X.MemoType.MEMO_TEXT, "hi").to_xdr()
            == b"\x00\x00\x00\x01\x00\x00\x00\x02hi\x00\x00"
        )

    def test_optional_absent_present(self):
        tb = X.TimeBounds(1, 2)
        tx = X.Transaction(
            sourceAccount=PK,
            fee=0,
            seqNum=0,
            timeBounds=None,
            memo=X.Memo.none(),
            operations=[],
            ext=0,
        )
        none_enc = tx.to_xdr()
        tx.timeBounds = tb
        some_enc = tx.to_xdr()
        # present adds bool(4) switch from 0->1 plus 16 payload bytes
        assert len(some_enc) == len(none_enc) + 16
        i = len(PK.to_xdr()) + 4 + 8  # source + fee + seq
        assert none_enc[i : i + 4] == b"\x00\x00\x00\x00"
        assert some_enc[i : i + 4] == b"\x00\x00\x00\x01"

    def test_negative_enum_discriminant(self):
        r = X.PaymentResult(X.PaymentResultCode.PAYMENT_UNDERFUNDED)
        assert r.to_xdr() == b"\xff\xff\xff\xfe"

    def test_envelope_type_prefix(self):
        assert (
            X.xdr_to_opaque(b"\x00" * 32, X.EnvelopeType.ENVELOPE_TYPE_TX)
            == b"\x00" * 32 + b"\x00\x00\x00\x02"
        )

    def test_ledger_header_layout(self):
        lh = X.LedgerHeader(ledgerVersion=1, ledgerSeq=9)
        enc = lh.to_xdr()
        assert len(enc) == 324
        assert enc[0:4] == b"\x00\x00\x00\x01"
        # ledgerSeq sits after version+prevHash+scpValue(48)+2 hashes
        off = 4 + 32 + 48 + 32 + 32
        assert enc[off : off + 4] == b"\x00\x00\x00\x09"


class TestUnions:
    def test_union_accessor(self):
        a = X.Asset.alphanum4(b"EUR", PK)
        assert a.alphaNum4.assetCode == b"EUR\x00"
        with pytest.raises(ValueError):
            _ = a.alphaNum12

    def test_union_bad_discriminant_rejected(self):
        with pytest.raises(XdrError):
            X.Asset.from_xdr(b"\x00\x00\x00\x07")

    def test_default_void_union(self):
        r = X.CreateAccountResult(X.CreateAccountResultCode.CREATE_ACCOUNT_MALFORMED)
        assert X.CreateAccountResult.from_xdr(r.to_xdr()) == r

    def test_void_arm_with_value_rejected(self):
        a = X.Asset(X.AssetType.ASSET_TYPE_NATIVE, b"junk")
        with pytest.raises(XdrError):
            a.to_xdr()

    def test_nested_quorum_set(self):
        q = X.SCPQuorumSet(
            2,
            [PK],
            [X.SCPQuorumSet(1, [PK, PK], []), X.SCPQuorumSet(1, [], [])],
        )
        assert X.SCPQuorumSet.from_xdr(q.to_xdr()) == q


# ---------------------------------------------------------------------------
# Property-based round trips (the reference uses autocheck/xdrpp generators,
# SURVEY.md §4; hypothesis is our equivalent).
# ---------------------------------------------------------------------------

# hypothesis's default deadline (200 ms an example) is wall time: under the
# suite's six workers one slow example of a correct codec fails the test
NO_DEADLINE = settings(deadline=None)

pubkeys = st.binary(min_size=32, max_size=32).map(X.PublicKey.from_ed25519)
hashes = st.binary(min_size=32, max_size=32)
values = st.binary(max_size=64)


ballots = st.builds(
    X.SCPBallot, st.integers(0, 2**32 - 1), values
)


@st.composite
def pledges(draw):
    t = draw(st.sampled_from(list(X.SCPStatementType)))
    if t == X.SCPStatementType.SCP_ST_PREPARE:
        v = X.SCPStatementPrepare(
            draw(hashes),
            draw(ballots),
            draw(st.none() | ballots),
            draw(st.none() | ballots),
            draw(st.integers(0, 2**32 - 1)),
            draw(st.integers(0, 2**32 - 1)),
        )
    elif t == X.SCPStatementType.SCP_ST_CONFIRM:
        v = X.SCPStatementConfirm(
            draw(hashes),
            draw(st.integers(0, 2**32 - 1)),
            draw(ballots),
            draw(st.integers(0, 2**32 - 1)),
        )
    elif t == X.SCPStatementType.SCP_ST_EXTERNALIZE:
        v = X.SCPStatementExternalize(
            draw(ballots), draw(st.integers(0, 2**32 - 1)), draw(hashes)
        )
    else:
        v = X.SCPNomination(
            draw(hashes),
            draw(st.lists(values, max_size=4)),
            draw(st.lists(values, max_size=4)),
        )
    return X.SCPStatementPledges(t, v)


envelopes = st.builds(
    X.SCPEnvelope,
    st.builds(X.SCPStatement, pubkeys, st.integers(0, 2**64 - 1), pledges()),
    st.binary(min_size=64, max_size=64),
)


@given(envelopes)
@NO_DEADLINE
def test_scp_envelope_roundtrip(env):
    assert X.SCPEnvelope.from_xdr(env.to_xdr()) == env


assets = st.one_of(
    st.just(X.Asset.native()),
    st.builds(lambda c, i: X.Asset.alphanum4(c, i), st.binary(min_size=1, max_size=4), pubkeys),
    st.builds(lambda c, i: X.Asset.alphanum12(c, i), st.binary(min_size=5, max_size=12), pubkeys),
)

operations = st.one_of(
    st.builds(
        lambda d, b: X.Operation(None, X.OperationBody(X.OperationType.CREATE_ACCOUNT, X.CreateAccountOp(d, b))),
        pubkeys,
        st.integers(0, 2**62),
    ),
    st.builds(
        lambda s, d, a, amt: X.Operation(
            s, X.OperationBody(X.OperationType.PAYMENT, X.PaymentOp(d, a, amt))
        ),
        st.none() | pubkeys,
        pubkeys,
        assets,
        st.integers(0, 2**62),
    ),
    st.builds(
        lambda d: X.Operation(None, X.OperationBody(X.OperationType.ACCOUNT_MERGE, d)),
        pubkeys,
    ),
    st.just(X.Operation(None, X.OperationBody(X.OperationType.INFLATION, None))),
)

memos = st.one_of(
    st.just(X.Memo.none()),
    st.builds(
        lambda t: X.Memo(X.MemoType.MEMO_TEXT, t),
        # string<28> bounds BYTES; keep generated text within that
        st.text(st.characters(codec="ascii", exclude_categories=["Cc", "Cs"]), max_size=28),
    ),
    st.builds(lambda i: X.Memo(X.MemoType.MEMO_ID, i), st.integers(0, 2**64 - 1)),
    st.builds(lambda h: X.Memo(X.MemoType.MEMO_HASH, h), hashes),
)

transactions = st.builds(
    X.Transaction,
    pubkeys,
    st.integers(0, 2**32 - 1),
    st.integers(0, 2**64 - 1),
    st.none() | st.builds(X.TimeBounds, st.integers(0, 2**64 - 1), st.integers(0, 2**64 - 1)),
    memos,
    st.lists(operations, min_size=1, max_size=5),
    st.just(0),
)

tx_envelopes = st.builds(
    X.TransactionEnvelope,
    transactions,
    st.lists(
        st.builds(X.DecoratedSignature, st.binary(min_size=4, max_size=4), st.binary(min_size=64, max_size=64)),
        max_size=3,
    ),
)


@given(tx_envelopes)
@NO_DEADLINE
def test_tx_envelope_roundtrip(te):
    assert X.TransactionEnvelope.from_xdr(te.to_xdr()) == te


@given(tx_envelopes)
@NO_DEADLINE
def test_stellar_message_roundtrip(te):
    m = X.StellarMessage(X.MessageType.TRANSACTION, te)
    am = X.AuthenticatedMessage.v0_of(7, m, b"\x00" * 32)
    assert X.AuthenticatedMessage.from_xdr(am.to_xdr()) == am


@given(st.binary(max_size=200))
@NO_DEADLINE
def test_unpack_never_crashes_unsafely(data):
    """Malformed input must raise XdrError, never other exceptions
    (this is what lets the overlay feed wire bytes straight into from_xdr,
    like xdrpp does for the reference's fuzzer, main/fuzz.cpp)."""
    for cls in (X.TransactionEnvelope, X.SCPEnvelope, X.StellarMessage, X.LedgerHeader):
        try:
            cls.from_xdr(data)
        except XdrError:
            pass


class TestXdrCopyAliasing:
    """Contracts behind the codec copy fast paths: value-semantics types
    are shared frozen instances; everything mutable stays independent."""

    def _account_entry(self):
        from stellar_tpu.xdr.entries import (
            AccountEntry,
            LedgerEntry,
            LedgerEntryData,
            LedgerEntryType,
            Signer,
        )
        from stellar_tpu.xdr.xtypes import PublicKey

        a = PublicKey.from_ed25519(b"\x01" * 32)
        s = PublicKey.from_ed25519(b"\x02" * 32)
        ae = AccountEntry(
            accountID=a,
            balance=100,
            seqNum=1 << 32,
            numSubEntries=1,
            inflationDest=None,
            flags=0,
            homeDomain="x",
            thresholds=b"\x01\x00\x00\x00",
            signers=[Signer(s, 1)],
        )
        return LedgerEntry(5, LedgerEntryData(LedgerEntryType.ACCOUNT, ae), 0)

    def test_mutable_parts_are_independent(self):
        from stellar_tpu.xdr.base import xdr_copy
        from stellar_tpu.xdr.entries import Signer
        from stellar_tpu.xdr.xtypes import PublicKey

        le = self._account_entry()
        cp = xdr_copy(le)
        orig = le.to_xdr()
        # mutate every mutable layer of the original
        le.lastModifiedLedgerSeq = 9
        le.data.value.balance = 1
        le.data.value.thresholds = b"\x02\x00\x00\x00"
        le.data.value.signers.append(
            Signer(PublicKey.from_ed25519(b"\x03" * 32), 2)
        )
        le.data.value.signers[0].weight = 7
        assert cp.to_xdr() == orig, "copy must be unaffected by the original"

    def test_value_semantics_instances_shared_and_frozen(self):
        import dataclasses

        import pytest

        from stellar_tpu.xdr.base import xdr_copy

        le = self._account_entry()
        cp = xdr_copy(le)
        assert cp.data.value.accountID is le.data.value.accountID
        with pytest.raises(dataclasses.FrozenInstanceError):
            cp.data.value.accountID.value = b"\x09" * 32
