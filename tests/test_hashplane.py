"""State-plane hash pipeline (ISSUE r22, bucket/hashplane.py).

The v2 bucket content hash — SHA256 of per-frame SHA-256 digests — has
three interchangeable backends (hashlib / native sighash.c pool / device
kernel).  This suite pins:

1. bit-identity across every backend that loads here, on real framed
   bucket buffers including the empty bucket;
2. the hostile surface — truncated/malformed frames raise ValueError on
   every path (the verify layer maps that to "corrupt");
3. fallback honesty — knob off and STELLAR_TPU_NO_NATIVE_HASH land on a
   backend that produces the SAME hash, never a silently different one;
4. the streaming ``BucketHasher`` (the bucket writers' ``hasher=`` slot)
   against the batch entry point, across its flush boundary;
5. background-vs-inline spill merges (bucket/mergeworker.py vs
   ``BACKGROUND_BUCKET_MERGE = False``) producing bit-identical bucket
   lists over enough ledgers to cross several spill cadences.

Device-backend legs compile tiny (nblocks<=2, N small) XLA shapes; the
pallas-interpret leg rides tests/test_sha256_device.py's slow marker.
"""

from __future__ import annotations

import hashlib
import struct

import pytest

from stellar_tpu.bucket import hashplane
from stellar_tpu.bucket.hashplane import (
    BucketHasher,
    HashlibBackend,
    backend_by_name,
    combine,
    get_backend,
    hash_frames,
    reset_backend_cache,
    split_frames,
)


def frame(body: bytes) -> bytes:
    return struct.pack(">I", 0x80000000 | len(body)) + body


def framed(*bodies) -> bytes:
    return b"".join(frame(b) for b in bodies)


@pytest.fixture(autouse=True)
def _clean_cache():
    reset_backend_cache()
    yield
    reset_backend_cache()


BODIES = [
    b"",  # minimal frame: header only
    b"x",
    bytes(range(51)),  # frame = 55 B (single-block padding edge)
    bytes(range(52)),  # frame = 56 B (spills into block 2)
    bytes(range(60)),  # frame = 64 B
    bytes(range(61)),  # frame = 65 B
    bytes(range(200)) + bytes(200),  # multi-block
    b"\xff" * 997,
]


def expected_v2(bodies):
    return combine(hashlib.sha256(frame(b)).digest() for b in bodies)


class TestFrameWalk:
    def test_split_roundtrip(self):
        frames = split_frames(framed(*BODIES))
        assert frames == [frame(b) for b in BODIES]

    def test_empty_buffer(self):
        assert split_frames(b"") == []

    @pytest.mark.parametrize(
        "buf",
        [
            b"\x80",  # truncated header
            b"\x80\x00\x00",  # still truncated
            struct.pack(">I", 5),  # continuation bit missing
            struct.pack(">I", 0x80000000 | 10) + b"short",  # truncated body
            struct.pack(">I", 0x80000000 | ((64 << 20) + 1)),  # oversized
            framed(b"good") + b"\x80\x00",  # good frame then garbage
        ],
    )
    def test_hostile_buffers_raise(self, buf):
        with pytest.raises(ValueError):
            split_frames(buf)
        # ...and through every backend's hash_frames
        with pytest.raises(ValueError):
            HashlibBackend().hash_frames(buf)
        native = backend_by_name("native")
        if native is not None:
            with pytest.raises(ValueError):
                native.hash_frames(buf)


class TestBackendBitIdentity:
    """Every backend that loads here produces the same (hash, count)."""

    def _loaded_backends(self):
        out = [HashlibBackend()]
        for name in ("native", "device-xla"):
            be = backend_by_name(name)
            if be is not None:
                out.append(be)
        return out

    def test_all_backends_agree_on_framed_buffer(self):
        buf = framed(*BODIES)
        want = (expected_v2(BODIES), len(BODIES))
        names = []
        for be in self._loaded_backends():
            assert be.hash_frames(buf) == want, be.name
            names.append(be.name)
        assert "hashlib" in names  # the oracle always runs

    def test_empty_bucket_hashes_like_empty_stream(self):
        want = (hashlib.sha256(b"").digest(), 0)
        for be in self._loaded_backends():
            assert be.hash_frames(b"") == want, be.name

    def test_device_oversized_frame_spills_to_hashlib(self):
        dev = backend_by_name("device-xla")
        if dev is None:
            pytest.skip("jax not importable")
        # one frame past DEVICE_MAX_BLOCKS compression blocks: the spill
        # class digests on the host, merged back in order
        big = bytes(range(256)) * ((hashplane.DEVICE_MAX_BLOCKS * 64) // 256 + 2)
        bodies = [b"small", big, b"also-small"]
        assert dev.hash_frames(framed(*bodies)) == (
            expected_v2(bodies), 3,
        )

    def test_native_batch_entry_points(self):
        from stellar_tpu import native

        mod = native.load_sighash()
        if mod is None:
            pytest.skip("native sha256_batch not built")
        frames = [frame(b) for b in BODIES]
        out = bytearray(32 * len(frames))
        mod.sha256_batch(frames, out)
        for i, f in enumerate(frames):
            assert out[32 * i : 32 * i + 32] == hashlib.sha256(f).digest()
        assert mod.bucket_hash_frames(framed(*BODIES)) == (
            expected_v2(BODIES), len(BODIES),
        )


class TestResolutionAndFallback:
    def test_default_resolution_never_device(self):
        from stellar_tpu.main.config import Config

        be = get_backend(Config())
        assert be.name in ("native", "hashlib")

    def test_knob_on_resolves_device(self):
        from stellar_tpu.main.config import Config

        if backend_by_name("device") is None:
            pytest.skip("jax not importable")
        cfg = Config()
        cfg.DEVICE_BUCKET_HASH = True
        assert get_backend(cfg).name.startswith("device")

    def test_no_native_env_forces_hashlib(self, monkeypatch):
        monkeypatch.setenv("STELLAR_TPU_NO_NATIVE_HASH", "1")
        reset_backend_cache()
        assert get_backend().name == "hashlib"

    def test_hash_frames_notes_stats(self):
        before = hashplane.stats.snapshot()
        buf = framed(*BODIES)
        assert hash_frames(buf) == (expected_v2(BODIES), len(BODIES))
        after = hashplane.stats.snapshot()
        assert after["bytes"] - before["bytes"] == len(buf)
        assert after["backend"] in ("native", "hashlib")

    def test_hash_file_matches_hash_frames(self, tmp_path):
        p = tmp_path / "bucket.xdr"
        p.write_bytes(framed(*BODIES))
        assert hashplane.hash_file(str(p)) == hash_frames(framed(*BODIES))
        corrupt = tmp_path / "corrupt.xdr"
        corrupt.write_bytes(framed(b"ok") + b"\x80\x00")
        with pytest.raises(ValueError):
            hashplane.hash_file(str(corrupt))


class TestBucketHasher:
    def test_streaming_matches_batch(self):
        h = BucketHasher()
        for b in BODIES:
            h.add(frame(b))
        assert h.count == len(BODIES)
        assert h.finish() == expected_v2(BODIES)

    def test_flush_boundary_equivalence(self, monkeypatch):
        """Force the ~4 MB batch flush to trip mid-stream: the combine
        must be insensitive to where the flush boundaries land."""
        monkeypatch.setattr(hashplane, "_FLUSH_BYTES", 128)
        h = BucketHasher()
        for b in BODIES:
            h.add(frame(b))
        assert h.finish() == expected_v2(BODIES)

    def test_empty_stream(self):
        h = BucketHasher()
        assert h.finish() == hashlib.sha256(b"").digest()


class TestConfigKnobs:
    def test_knob_defaults_and_validation(self):
        from stellar_tpu.main.config import Config

        cfg = Config()
        assert cfg.DEVICE_BUCKET_HASH is False
        assert cfg.BACKGROUND_BUCKET_MERGE is True
        cfg.validate()
        for knob in ("DEVICE_BUCKET_HASH", "BACKGROUND_BUCKET_MERGE"):
            cfg = Config()
            setattr(cfg, knob, True)
            cfg.validate()
            setattr(cfg, knob, "yes")
            with pytest.raises(ValueError):
                cfg.validate()

    def test_from_dict_plumbs(self):
        from stellar_tpu.main.config import Config

        cfg = Config.from_dict(
            {"DEVICE_BUCKET_HASH": True, "BACKGROUND_BUCKET_MERGE": False}
        )
        assert cfg.DEVICE_BUCKET_HASH is True
        assert cfg.BACKGROUND_BUCKET_MERGE is False


class TestBackgroundMergeDifferential:
    """bucket/mergeworker.py vs inline merging: the output hash cannot
    depend on WHERE the deterministic merge ran."""

    def _run_ledgers(self, instance, background, n=70):
        from stellar_tpu.bucket.bucketlist import BucketList
        from stellar_tpu.main.application import Application
        from stellar_tpu.tx import testutils as T
        from stellar_tpu.util.clock import VirtualClock
        from tests.test_bucket import account_entry
        from stellar_tpu.ledger.entryframe import ledger_key_of

        clock = VirtualClock()
        cfg = T.get_test_config(instance)
        cfg.BACKGROUND_BUCKET_MERGE = background
        app = Application(clock, cfg, new_db=True)
        try:
            bl = BucketList()
            hashes = []
            for seq in range(1, n + 1):
                live = [
                    account_entry(seq % 13, balance=seq),
                    account_entry(500 + seq),
                ]
                dead = []
                if seq % 7 == 0 and seq > 7:
                    dead = [ledger_key_of(account_entry(500 + seq - 7))]
                bl.add_batch(app, seq, live, dead)
                hashes.append(bl.get_hash())
            return hashes
        finally:
            app.database.close()
            clock.shutdown()

    def test_background_and_inline_bit_identical(self):
        # 70 ledgers cross the level-0 and level-1 spill cadences many
        # times over — every FutureBucket merge runs on the worker pool
        # in one tree and synchronously in the other
        bg = self._run_ledgers(171, background=True)
        inline = self._run_ledgers(172, background=False)
        assert bg == inline
