"""State-plane hash pipeline: batched per-record bucket hashing behind a
backend seam (ISSUE r22, ROADMAP #4).

The v2 bucket content hash is

    H(bucket) = SHA256( d_1 ‖ d_2 ‖ … ‖ d_n ),   d_i = SHA256(frame_i)

where ``frame_i`` is the full i-th record as written (4-byte RFC 5531
header ‖ XDR body).  Bucket hashes are framework-local (bucket/bucket.py
header note), so the scheme is free to differ from the reference's raw
stream hash — what it buys is parallelism: the per-record digests are an
embarrassingly parallel batch (the device kernel's lanes, the C pool's
tiles), and the sequential combine touches only 32 bytes per record
(~3% of the stream at typical entry sizes).  Every producer and verifier
moved together: ``Bucket.fresh``, ``_write_merged``, the native merge
(``bucket_merge_v2``), ``verify_bucket_file``, and catchup's archive
adoption — so the hash stays self-consistent end to end, including
bucket file names and the HistoryArchiveState combinators above them
(level hash = H(curr‖snap), list hash — unchanged shapes, new leaf
values).  The empty stream hashes to SHA256(b"") under both schemes.

Three interchangeable backends, all bit-identical (pinned by
tests/test_hashplane.py):

- ``device``  — the batched multi-block SHA-256 kernel (ops/sha256.py,
  XLA or Pallas), knob ``Config.DEVICE_BUCKET_HASH``.  Oversized frames
  (> ``DEVICE_MAX_BLOCKS`` compression blocks) spill to hashlib — same
  digests, merged in order.
- ``native``  — native/sighash.c's ``sha256_batch`` /
  ``bucket_hash_frames``: GIL-released, tile-fanned over the pthread
  pool.  The default whenever the extension builds.
- ``hashlib`` — the always-available last resort (and the differential
  oracle), forced by ``STELLAR_TPU_NO_NATIVE_HASH=1``.
"""

from __future__ import annotations

import hashlib
import os
import struct
import threading
import time
from typing import List, Optional, Tuple

_MAX_FRAME = 64 << 20  # util/xdrstream.py's body cap
_FLUSH_BYTES = 4 << 20  # BucketHasher batches this much before digesting
DEVICE_MAX_BLOCKS = 64  # frames above 64 SHA blocks (~4 KB) skip the device


def split_frames(buf) -> List[bytes]:
    """A framed record buffer -> the list of full frames (header+body).
    Raises ValueError on a truncated/malformed frame — the verify layer
    maps that to "corrupt"."""
    frames = []
    view = memoryview(buf)
    off, n = 0, len(view)
    while off < n:
        if off + 4 > n:
            raise ValueError("truncated bucket frame header")
        (hdr,) = struct.unpack_from(">I", view, off)
        if not hdr & 0x80000000:
            raise ValueError("bucket frame missing continuation bit")
        ln = hdr & 0x7FFFFFFF
        if ln > _MAX_FRAME:
            raise ValueError("oversized bucket frame")
        end = off + 4 + ln
        if end > n:
            raise ValueError("truncated bucket frame body")
        frames.append(bytes(view[off:end]))
        off = end
    return frames


def combine(digests) -> bytes:
    """The ordered digest combine — the only sequential stage."""
    comb = hashlib.sha256()
    for d in digests:
        comb.update(d)
    return comb.digest()


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------


class BucketHashBackend:
    """One way to produce per-frame SHA-256 digests in batch."""

    name = "?"

    def digests(self, frames: List[bytes]) -> List[bytes]:
        raise NotImplementedError

    def hash_frames(self, buf) -> Tuple[bytes, int]:
        """(v2 hash, record count) of a whole framed buffer."""
        frames = split_frames(buf)
        return combine(self.digests(frames)), len(frames)

    def hash_file(self, path: str) -> Tuple[bytes, int]:
        with open(path, "rb") as f:
            return self.hash_frames(f.read())


class HashlibBackend(BucketHashBackend):
    name = "hashlib"

    def digests(self, frames):
        return [hashlib.sha256(f).digest() for f in frames]


class NativeBackend(BucketHashBackend):
    """native/sighash.c: GIL-released, pthread-pool-fanned batches."""

    name = "native"

    def __init__(self, mod):
        self._mod = mod

    def digests(self, frames):
        out = bytearray(32 * len(frames))
        self._mod.sha256_batch(frames, out)
        return [bytes(out[32 * i : 32 * i + 32]) for i in range(len(frames))]

    def hash_frames(self, buf):
        # one C call: frame walk + parallel digests + ordered combine
        return self._mod.bucket_hash_frames(bytes(buf))

    def hash_file(self, path):
        from .. import native

        res = native.bucket_hash_v2_file(path)
        if res is not None:
            return res
        # C reported failure (unreadable or malformed): re-walk in
        # Python for the precise verdict (raises ValueError on corrupt)
        return super().hash_file(path)


class DeviceBackend(BucketHashBackend):
    """ops/sha256.py: the batched multi-block kernel.  Frames are
    size-classed into power-of-two ``max_blocks`` shapes so jit reuse is
    bounded; frames past DEVICE_MAX_BLOCKS spill to hashlib (bucket
    entries are a few hundred bytes — the spill class is empty in
    practice)."""

    def __init__(self, pallas: bool = False, interpret: bool = False):
        self.pallas = pallas
        self.interpret = interpret
        self.name = "device-pallas" if pallas else "device-xla"

    def digests(self, frames):
        import jax.numpy as jnp

        from ..ops import sha256 as dev

        out: List[Optional[bytes]] = [None] * len(frames)
        classes: dict = {}
        for i, f in enumerate(frames):
            nb = dev.blocks_for(len(f))
            if nb > DEVICE_MAX_BLOCKS:
                out[i] = hashlib.sha256(f).digest()
                continue
            cap = 1
            while cap < nb:
                cap *= 2
            classes.setdefault(cap, []).append(i)
        for cap, idxs in classes.items():
            batch = [frames[i] for i in idxs]
            if self.pallas:
                from ..ops.ed25519_pallas import NT

                pad = (-len(batch)) % NT
                packed, counts = dev.pack_frames(
                    batch + [b""] * pad, max_blocks=cap
                )
                rows = dev.sha256_pallas(
                    jnp.asarray(packed),
                    jnp.asarray(counts),
                    interpret=self.interpret,
                )
            else:
                packed, counts = dev.pack_frames(batch, max_blocks=cap)
                rows = dev._jit_rows_from_packed(
                    jnp.asarray(packed), jnp.asarray(counts)
                )
            import numpy as np

            arr = np.asarray(rows, dtype=np.int32).astype(np.uint8)
            for j, i in enumerate(idxs):
                out[i] = arr[:, j].tobytes()
        return out  # type: ignore[return-value]


# ---------------------------------------------------------------------------
# resolution + throughput stats
# ---------------------------------------------------------------------------


class _Stats:
    """Whole-process hash-plane throughput ledger: bytes hashed and wall
    seconds per backend, read by selfcheck's boot report."""

    def __init__(self):
        self._lock = threading.Lock()
        self._bytes = 0  # analysis: locked-by _lock
        self._seconds = 0.0  # analysis: locked-by _lock
        self._backend_name = ""  # analysis: locked-by _lock

    def note(self, nbytes: int, seconds: float, backend: str) -> None:
        with self._lock:
            self._bytes += nbytes
            self._seconds += seconds
            self._backend_name = backend

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "bytes": self._bytes,
                "seconds": self._seconds,
                "backend": self._backend_name,
            }

    @staticmethod
    def rate_mb_per_sec(before: dict, after: dict) -> float:
        db = after["bytes"] - before["bytes"]
        dt = after["seconds"] - before["seconds"]
        return round(db / dt / 1e6, 1) if dt > 0 else 0.0


stats = _Stats()

_cache_lock = threading.Lock()
_cache: dict = {}  # guarded by _cache_lock (module-level, not a field)


def backend_by_name(
    name: str, interpret: bool = False
) -> Optional[BucketHashBackend]:
    """An explicit backend instance (bench/profile A/B legs), or None
    when that backend can't load here."""
    if name == "hashlib":
        return HashlibBackend()
    if name == "native":
        from .. import native

        mod = native.load_sighash()
        if mod is None:
            return None
        return NativeBackend(mod)
    if name in ("device", "device-xla", "device-pallas"):
        try:
            import jax

            pallas = (
                name == "device-pallas"
                or (name == "device" and jax.default_backend() == "tpu")
            )
            return DeviceBackend(pallas=pallas, interpret=interpret)
        except Exception:
            return None
    raise ValueError(f"unknown bucket hash backend {name!r}")


def get_backend(config=None) -> BucketHashBackend:
    """Resolve the active backend: device when Config.DEVICE_BUCKET_HASH
    (and jax imports), else native (when the extension builds), else
    hashlib."""
    want_device = bool(config is not None and getattr(
        config, "DEVICE_BUCKET_HASH", False
    ))
    no_native = bool(os.environ.get("STELLAR_TPU_NO_NATIVE_HASH"))
    key = (want_device, no_native)
    with _cache_lock:
        hit = _cache.get(key)
    if hit is not None:
        return hit
    backend: Optional[BucketHashBackend] = None
    if want_device:
        backend = backend_by_name("device")
    if backend is None and not no_native:
        backend = backend_by_name("native")
    if backend is None:
        backend = HashlibBackend()
    with _cache_lock:
        _cache[key] = backend
    return backend


def reset_backend_cache() -> None:
    """Test hook: drop resolved backends (knob/env changes re-resolve)."""
    with _cache_lock:
        _cache.clear()


# ---------------------------------------------------------------------------
# the wired entry points (bucket.py / manager.py / catchup call these)
# ---------------------------------------------------------------------------


def hash_frames(buf, config=None) -> Tuple[bytes, int]:
    """(v2 bucket hash, record count) of a framed record buffer.
    Raises ValueError on a malformed/truncated frame."""
    backend = get_backend(config)
    t0 = time.perf_counter()
    out = backend.hash_frames(buf)
    stats.note(len(buf), time.perf_counter() - t0, backend.name)
    return out


def hash_file(path: str, config=None) -> Tuple[bytes, int]:
    """(v2 bucket hash, record count) of a bucket file on disk.  Raises
    OSError when unreadable, ValueError when malformed."""
    backend = get_backend(config)
    t0 = time.perf_counter()
    out = backend.hash_file(path)
    try:
        nbytes = os.path.getsize(path)
    except OSError:
        nbytes = 0
    stats.note(nbytes, time.perf_counter() - t0, backend.name)
    return out


class BucketHasher:
    """Drop-in for crypto.sha.SHA256 in the bucket writers (the
    ``hasher=`` slot of util/xdrstream.XDROutputFileStream): ``add``
    takes EXACTLY ONE full frame per call — which is what write_one
    feeds it — and ``finish`` returns the v2 hash.  Frames batch up to
    ~4 MB before a backend digest pass, so memory stays bounded on
    million-record merges while batches stay big enough to fan out."""

    def __init__(self, config=None):
        self._backend = get_backend(config)
        self._comb = hashlib.sha256()
        self._pending: List[bytes] = []
        self._pending_bytes = 0
        self._count = 0
        self._finished = False

    def add(self, frame) -> None:
        assert not self._finished, "hash already finished"
        self._pending.append(bytes(frame))
        self._pending_bytes += len(frame)
        self._count += 1
        if self._pending_bytes >= _FLUSH_BYTES:
            self._flush()

    def _flush(self) -> None:
        if not self._pending:
            return
        t0 = time.perf_counter()
        for d in self._backend.digests(self._pending):
            self._comb.update(d)
        stats.note(
            self._pending_bytes,
            time.perf_counter() - t0,
            self._backend.name,
        )
        self._pending = []
        self._pending_bytes = 0

    @property
    def count(self) -> int:
        return self._count

    def finish(self) -> bytes:
        self._flush()
        self._finished = True
        return self._comb.digest()
