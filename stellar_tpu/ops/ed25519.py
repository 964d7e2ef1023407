"""Batched ed25519 verification on TPU (JAX).

The split (SURVEY.md §7 hard-part #1, BASELINE.json north star):

- **host**: libsodium's strict input gate (canonical s, canonical A, small-
  order A/R rejection) + SHA-512(R‖A‖M) mod L + packed staging, all in one
  GIL-releasing C pass per chunk (native/sighash.c; hashlib/numpy fallback
  mirrors ops/ref25519.strict_input_ok);
- **device**: point decompress of A (field exponentiation), Straus
  double-scalar multiplication R' = s·B + h·(−A) with 4-bit windows
  (shared doublings, niels tables, complete a=−1 twisted Edwards formulas),
  point encoding, byte compare against R.

Verification semantics are bit-exact with libsodium
``crypto_sign_verify_detached`` (differential suite: tests/test_ed25519_tpu.py).

Curve math dataflow is pure int32; batch axis N rides the TPU vector lanes
(layout notes in ops/fe.py).  One compile per padded batch size.
"""

from __future__ import annotations

import hashlib
import os
import threading
import time
from functools import partial
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..util import xlog
from . import STAGES, SUMS, StageTally, compile_events, fe, programs
from . import ref25519 as ref

_log = xlog.logger("Tx")

D = ref.D
D2 = (2 * ref.D) % ref.P
SQRT_M1 = ref.SQRT_M1
L = ref.L

_D_FE = fe.const_fe(D)
_D2_FE = fe.const_fe(D2)
_SQRT_M1_FE = fe.const_fe(SQRT_M1)

WINDOWS = 64  # 4-bit windows over 256-bit scalars
PIPELINE_DEPTH = 2  # max in-flight device chunks in BatchVerifier.verify


# ---------------------------------------------------------------------------
# point ops — extended coordinates (X:Y:Z:T), a=-1 complete formulas
# ---------------------------------------------------------------------------


def point_identity(n, dtype=jnp.int32):
    zero = jnp.zeros((fe.LIMBS, n), dtype)
    one = fe.one_fe(n, dtype)
    return (zero, one, one, zero)


def point_add(p, q):
    """General extended + extended (add-2008-hwcd-3 shape, 9M)."""
    X1, Y1, Z1, T1 = p
    X2, Y2, Z2, T2 = q
    a = fe.mul(fe.sub(Y1, X1), fe.sub(Y2, X2))
    b = fe.mul(fe.add(Y1, X1), fe.add(Y2, X2))
    c = fe.mul(fe.mul(T1, T2), fe._c("D2", _D2_FE))
    d = fe.mul_small(fe.mul(Z1, Z2), 2)
    e = fe.sub(b, a)
    f = fe.sub(d, c)
    g = fe.add(d, c)
    h = fe.add(b, a)
    return (fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), fe.mul(e, h))


def point_add_niels(p, n, need_t: bool = True):
    """Extended + precomputed niels (YpX, YmX, T2d, Z2): 8M (7M w/o T).

    ``need_t=False`` when the result feeds a doubling (which ignores T)."""
    X1, Y1, Z1, T1 = p
    YpX2, YmX2, T2d2, Z22 = n
    a = fe.mul(fe.sub(Y1, X1), YmX2)
    b = fe.mul(fe.add(Y1, X1), YpX2)
    c = fe.mul(T1, T2d2)
    d = fe.mul(Z1, Z22)
    e = fe.sub(b, a)
    f = fe.sub(d, c)
    g = fe.add(d, c)
    h = fe.add(b, a)
    t = fe.mul(e, h) if need_t else jnp.zeros_like(X1)
    return (fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), t)


def point_double(p, need_t: bool = True):
    """dbl-2008-hwcd with a=-1: 4S + 4M (3M with ``need_t=False``).

    Doubling never reads the input T, so inside a doubling chain only the
    last double before an addition needs to produce T — the others skip
    the E·H multiply and return a zero T placeholder.
    """
    X1, Y1, Z1, _ = p
    a = fe.sqr(X1)
    b = fe.sqr(Y1)
    c = fe.mul_small(fe.sqr(Z1), 2)
    d = fe.neg(a)  # a_coef = -1
    e = fe.sub(fe.sub(fe.sqr(fe.add(X1, Y1)), a), b)
    g = fe.add(d, b)
    f = fe.sub(g, c)
    h = fe.sub(d, b)
    t = fe.mul(e, h) if need_t else jnp.zeros_like(X1)
    return (fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), t)


def to_niels(p):
    X, Y, Z, T = p
    return (
        fe.add(Y, X),
        fe.sub(Y, X),
        fe.mul(T, fe._c("D2", _D2_FE)),
        fe.mul_small(Z, 2),
    )


def point_negate(p):
    X, Y, Z, T = p
    return (fe.neg(X), Y, Z, fe.neg(T))


def compress(p, batch_inv: bool = False):
    """-> ((32, N) bytes, x-parity already folded into byte 31).

    ``batch_inv`` switches the Z inversion to fe.inv_batch (tree-product
    Montgomery inversion across lanes) — correct only when the batch axis
    is local to the caller (Pallas tile / unsharded XLA batch; NOT under a
    mesh-sharded jit, where cross-lane slicing would force collectives) and
    when zero-Z lanes are masked downstream (inv_batch returns garbage for
    them, not 0)."""
    X, Y, Z, _ = p
    zinv = fe.inv_batch(Z) if batch_inv else fe.inv(Z)
    x = fe.mul(X, zinv)
    y = fe.mul(Y, zinv)
    by = fe.bytes_from_limbs(fe.canonical(y))
    sign = fe.parity(x)
    by = fe.set_row(by, 31, by[31] + (sign << 7))
    return by


def decompress(y_limbs, sign):
    """-> (point, fail) matching ref25519.decompress for canonical y."""
    one = fe.one_fe(y_limbs.shape[1:], y_limbs.dtype)
    yy = fe.sqr(y_limbs)
    u = fe.sub(yy, one)
    v = fe.add(fe.mul(yy, fe._c("D", _D_FE)), one)
    v3 = fe.mul(fe.sqr(v), v)
    v7 = fe.mul(fe.sqr(v3), v)
    x = fe.mul(fe.mul(u, v3), fe.pow_p58(fe.mul(u, v7)))
    vxx = fe.mul(v, fe.sqr(x))
    ok1 = fe.eq(vxx, u)
    ok2 = fe.eq(vxx, fe.neg(u))
    x = fe.select(ok2, fe.mul(x, fe._c("SQRT_M1", _SQRT_M1_FE)), x)
    fail = ~(ok1 | ok2)
    fail = fail | (fe.is_zero(x) & (sign == 1))
    flip = fe.parity(x) != sign
    x = fe.select(flip, fe.neg(x), x)
    return (x, y_limbs, one, fe.mul(x, y_limbs)), fail


# ---------------------------------------------------------------------------
# fixed-base table (host-precomputed from the reference implementation)
# ---------------------------------------------------------------------------


def _base_niels_table_np() -> np.ndarray:
    """(4, 16, 20) int32: niels components of k*B for k=0..15."""
    tab = np.zeros((4, 16, fe.LIMBS), dtype=np.int32)
    pt = ref.IDENT
    B = ref.base_point()
    for k in range(16):
        x, y, z, t = pt
        zinv = ref.fe_inv(z)
        xa, ya = x * zinv % ref.P, y * zinv % ref.P
        ta = xa * ya % ref.P
        tab[0, k] = fe.int_to_limbs((ya + xa) % ref.P)
        tab[1, k] = fe.int_to_limbs((ya - xa) % ref.P)
        tab[2, k] = fe.int_to_limbs(ta * D2 % ref.P)
        tab[3, k] = fe.int_to_limbs(2)
        pt = ref.point_add(pt, B)
    return tab


_BASE_TABLE = jnp.asarray(_base_niels_table_np())  # (4, 16, 20)


def _select_base(nib):
    """nib (N,) -> niels tuple of (20, N) from the static base table."""
    onehot = (nib[None, :] == jnp.arange(16, dtype=nib.dtype)[:, None]).astype(
        jnp.int32
    )  # (16, N)
    comps = jnp.einsum("kn,ckl->cln", onehot, _BASE_TABLE)  # (4, 20, N)
    return (comps[0], comps[1], comps[2], comps[3])


def _select_dyn(table, nib):
    """table: tuple of 4 arrays (20, 16, N); nib (N,)."""
    onehot = (nib[None, :] == jnp.arange(16, dtype=nib.dtype)[:, None]).astype(
        jnp.int32
    )  # (16, N)
    return tuple(jnp.einsum("kn,lkn->ln", onehot, t) for t in table)


def _build_a_table(neg_a):
    """niels table of k*(-A) for k=0..15: tuple of 4 arrays (20, 16, N).

    Sequential adds run under lax.scan (15 iterations, one traced body);
    the niels conversion is then vectorized across all 16 entries at once —
    fe ops are shape-polymorphic in the trailing dims.
    """
    n = neg_a[0].shape[1]

    def step(p, _):
        p2 = point_add(p, neg_a)
        return p2, p2

    _, mults = jax.lax.scan(step, point_identity(n), None, length=15)
    # mults: 4 arrays (15, 20, N); prepend identity and move limbs first
    ident = point_identity(n)
    full = tuple(
        jnp.concatenate([ident[c][None], mults[c]], axis=0).transpose(1, 0, 2)
        for c in range(4)
    )  # (20, 16, N)
    return to_niels(full)


# ---------------------------------------------------------------------------
# the verify kernel
# ---------------------------------------------------------------------------


def verify_kernel(a_bytes, r_bytes, s_nibs, h_nibs, batch_inv: bool = False):
    """All-device batched check R' == R.

    a_bytes   (32,N) — public key A bytes (little-endian, sign in bit 255)
    r_bytes   (32,N) — signature R bytes (to compare against)
    s_nibs    (64,N) — s scalar nibbles, little-endian
    h_nibs    (64,N) — h = SHA512(R‖A‖M) mod L nibbles, little-endian
    batch_inv — use lane-tree Montgomery inversion in compress; only valid
                when the batch axis is unsharded (see compress)
    returns   (N,) bool
    """
    a_sign = a_bytes[31] >> 7
    a_masked = fe.set_row(a_bytes, 31, a_bytes[31] & 0x7F)
    a_y_limbs = fe.limbs_from_bytes(a_masked)
    a_pt, fail = decompress(a_y_limbs, a_sign)
    neg_a = point_negate(a_pt)
    a_table = _build_a_table(neg_a)

    n = a_bytes.shape[1]

    def body(i, acc):
        t = WINDOWS - 1 - i
        for k in range(4):
            # only the last double feeds an addition, which is the sole
            # consumer of T — the first three skip the E·H multiply
            acc = point_double(acc, need_t=(k == 3))
        acc = point_add_niels(acc, _select_base(s_nibs[t]))
        # the next consumer is the following window's doubling: no T needed
        acc = point_add_niels(acc, _select_dyn(a_table, h_nibs[t]), need_t=False)
        return acc

    acc = jax.lax.fori_loop(0, WINDOWS, body, point_identity(n))
    enc = compress(acc, batch_inv=batch_inv)
    match = jnp.all(enc == r_bytes, axis=0)
    return match & ~fail


# ---------------------------------------------------------------------------
# host orchestration
# ---------------------------------------------------------------------------


def _nibbles_np(scalars_le_bytes: np.ndarray) -> np.ndarray:
    """(N, 32) uint8 -> (64, N) int32 nibbles little-endian."""
    lo = scalars_le_bytes & 0x0F
    hi = scalars_le_bytes >> 4
    inter = np.empty((scalars_le_bytes.shape[0], 64), dtype=np.int32)
    inter[:, 0::2] = lo
    inter[:, 1::2] = hi
    return np.ascontiguousarray(inter.T)


def _nibbles_dev(b):
    """(32, N) byte rows -> (64, N) int32 little-endian nibbles, on device
    (the packed-upload path widens and splits inside the jit program)."""
    b = b.astype(jnp.int32)
    return jnp.stack([b & 0x0F, b >> 4], axis=1).reshape(64, -1)


def _verify_packed(p, batch_inv: bool = False):
    """verify_kernel over the packed (128, N) uint8 staging layout
    (rows 0:32 A, 32:64 R, 64:96 s, 96:128 h)."""
    a = p[0:32].astype(jnp.int32)
    r = p[32:64].astype(jnp.int32)
    return verify_kernel(
        a, r, _nibbles_dev(p[64:96]), _nibbles_dev(p[96:128]),
        batch_inv=batch_inv,
    )


def _verify_packed_device_hash(p, batch_inv: bool = False):
    """The DEVICE-HASH fusion: SHA-512(R‖A‖M) mod L computed on device
    (ops/sha512.py) from the packed (160, N) raw-byte staging layout,
    then the same verify kernel — one jit, no host hash.  flag=0 lanes
    (multi-block residuals, torsion-proof columns) carry a host h in
    rows 96:128 and bypass the device hash by selection."""
    from . import sha512 as dsha

    a = p[0:32].astype(jnp.int32)
    r = p[32:64].astype(jnp.int32)
    h = dsha.h_rows_from_packed(p)
    return verify_kernel(
        a, r, _nibbles_dev(p[64:96]), _nibbles_dev(h),
        batch_inv=batch_inv,
    )


# sign-masked small-order encodings for the native gate (identical table
# to the Python gate's — both derive from ref25519.small_order_blacklist)
_BLACKLIST = b"".join(ref.small_order_blacklist())


class _Staged(NamedTuple):
    """One staged chunk: the packed upload buffer(s) plus the host
    gate verdicts that mask the device results at drain time.

    Unsharded: ``packed`` is the single (128, bucket) buffer.  Under a
    mesh it is a LIST of per-shard (128, bucket // n_shards) buffers —
    each uploads straight to its chip (``_upload_sharded``)."""

    packed: object      # (128, bucket) uint8 C-contiguous, or per-shard list
    ok: np.ndarray      # (n,) bool — strict-input gate results
    n: int              # live lanes (bucket - n are zero padding)
    bufs: tuple         # staging-pool token(s); released after drain


class _StagingPool:
    """Reusable preallocated staging buffers, keyed by (rows, bucket)
    shape — 128 rows for the host-hash layout, sha512.DH_ROWS for the
    device-hash raw layout.

    ``jnp.asarray`` may alias host memory on the CPU backend, so a buffer
    returns to the pool only AFTER its chunk's results have been drained
    (the device computation that reads it has completed) — never while a
    dispatch may still be in flight.  Pool size is naturally bounded by
    the pipeline depth (at most depth+1 chunks hold buffers at once)."""

    def __init__(self):
        self._free = {}
        self._lock = threading.Lock()

    def acquire(self, bucket: int, rows: int = 128):
        key = (rows, bucket)
        with self._lock:
            lst = self._free.get(key)
            if lst:
                return lst.pop()
        return (
            np.empty((rows, bucket), dtype=np.uint8),
            np.empty(bucket, dtype=np.uint8),
        )

    def release(self, bufs) -> None:
        if bufs is None:
            return
        if not isinstance(bufs[0], np.ndarray):
            # a mesh chunk's per-shard buffer list: release every pair
            for pair in bufs:
                self.release(pair)
            return
        with self._lock:
            self._free.setdefault(bufs[0].shape, []).append(bufs)


# where a bucket's lowered program came from (``_FirstDispatch.program``)
PROGRAM_STORED = "stored"
PROGRAM_EXPORTED = "exported"
PROGRAM_TRACED = "traced"


class _FirstDispatch:
    """The account open on a thread while it dispatches a bucket this
    process has not run yet (``ops/__init__.py`` ``CompileEvents``): what
    JAX reports there of the program's trace, lowering and compilation,
    between ``start`` and ``end`` on ``time.monotonic`` — the tracer's clock
    and the device profile's (``trace.sync.<ns>``), so a record can be laid
    over ``/trace`` and an ``.xplane.pb``.

    ``program`` says where the bucket's lowered program came from
    (``BatchVerifier._first_program``): ``"stored"`` — loaded from the
    program store, so the trace here is the wrapper's and the lowering the
    stored module's parse; ``"exported"`` — traced, lowered and stored by
    this process; ``"traced"`` — the store could not be used
    (``program_error``: the exception's class) and ``jax.jit`` traced the
    kernel as it did before there was a store."""

    def __init__(self, bucket: int, caller: Optional[str]):
        self.bucket = bucket
        self.caller = caller
        self.seen = dict.fromkeys((*STAGES.values(), *SUMS.values()), 0.0)
        self.seen.update(cache_hits=0, cache_misses=0)
        self.program = PROGRAM_TRACED
        self.program_error: Optional[str] = None
        # the key, the read and the deserialize: no stage event lies in it
        self.program_load_s = 0.0
        # the stored program's file, once the key is known
        self.program_path: Optional[str] = None
        self.start = time.monotonic()

    def add(self, field: str, value, bucket) -> None:
        self.seen[field] += value

    def trace_lower_s(self) -> float:
        return self.seen["trace_s"] + self.seen["lower_s"]

    def close(self) -> dict:
        """The record of ``stats()["first_dispatch"]["buckets"]``."""
        end = time.monotonic()
        seen = {k: max(v, 0) for k, v in self.seen.items()}
        staged = seen["trace_s"] + seen["lower_s"] + seen["compile_s"]
        hits, misses = seen["cache_hits"], seen["cache_misses"]
        rec = {
            "bucket": self.bucket,
            "start": self.start,
            "end": end,
            "trace_s": seen["trace_s"],
            "lower_s": seen["lower_s"],
            # XLA / Mosaic on a miss; on a hit the read and the load
            "compile_s": seen["compile_s"],
            "cache_retrieval_s": seen["cache_retrieval_s"],
            # "off": JAX asked the persistent cache nothing, or compiled
            # for under the second from which it writes an entry
            "cache": "miss" if misses else "hit" if hits else "off",
            "cache_hits": hits,
            "cache_misses": misses,
            "program": self.program,
            "program_load_s": self.program_load_s,
            # the upload, the enqueue, on "exported" the serialise and the
            # write, and what JAX does not report
            "rest_s": max(end - self.start - staged - self.program_load_s, 0.0),
            "caller": self.caller,
            "thread": threading.current_thread().name,
        }
        if hits:
            rec["compile_time_saved_s"] = seen["compile_time_saved_s"]
        if self.program_error is not None:
            rec["program_error"] = self.program_error
        return rec


# of a first dispatch's record, what its span carries beside ``first``
_FIRST_SPAN_ATTRS = (
    "trace_s",
    "lower_s",
    "compile_s",
    "cache_retrieval_s",
    "cache",
    "compile_time_saved_s",
    "rest_s",
    "caller",
    "program",
)

# and what stats() sums over the records
_FIRST_SUMS = (
    "trace_s",
    "lower_s",
    "compile_s",
    "cache_retrieval_s",
    "cache_hits",
    "cache_misses",
)


def _union_seconds(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


# What the nodes of one process share (``BatchVerifier(shared_programs=
# True)``, as every Application's backend asks): by everything that decides
# the traced kernel, the kernel, what a dispatch of each bucket calls, and
# the record of each bucket's first dispatch in this process.  A second node
# — a catch-up's fresh one, a simulation's — then loads, traces and compiles
# nothing for a bucket the process has run.
_process_programs: dict = {}
_process_programs_lock = threading.Lock()


class BatchVerifier:
    """Pads batches to pow-2 buckets (one XLA compile per bucket), runs the
    kernel, scatters results; host gate verdicts mask the device results,
    so a gate-rejected lane can never report True (and a chunk whose lanes
    ALL fail the gate skips its device round-trip entirely).

    ``backend="auto"`` picks the Pallas kernel (ops/ed25519_pallas.py —
    measured 4× the XLA lowering on v5e in round 3) on a real
    accelerator and the plain XLA kernel on CPU.  With a mesh, the Pallas
    kernel runs PER SHARD under shard_map (each chip grids its local
    slice of the batch; no cross-shard communication — XLA inserts only
    the output all-gather), so multi-chip keeps the fast kernel."""

    # class-level default: a harness that builds the planning state by hand
    # (tests) shares nothing
    _process_firsts: Optional[dict] = None

    def __init__(
        self,
        max_batch: int = 4096,
        mesh=None,
        min_device_batch: int = 16,
        backend: str = "auto",
        streams: Optional[int] = None,
        host_assist: Optional[float] = None,
        native_hash: Optional[bool] = None,
        device_hash: Optional[bool] = None,
        tracer=None,
        shared_programs: bool = False,
    ):
        from ..trace import NULL_TRACER

        self._tracer = tracer if tracer is not None else NULL_TRACER
        self.max_batch = max_batch
        self.min_device_batch = min_device_batch
        self.mesh = mesh
        # Device-resident hash stage (ops/sha512.py; Config.DEVICE_HASH /
        # STELLAR_TPU_DEVICE_HASH): the single-block SHA-512(R‖A‖M) mod L
        # runs fused ahead of the verify kernel in the same jit, staging
        # uploads RAW bytes (160 rows/item) and the host keeps only the
        # strict gate; multi-block (>111-byte preimage) residuals ride
        # the C hash path and merge via the flag row.  Off (default, like
        # SIG_MESH) = the host-hash 128-row path, bit-exact either way.
        if device_hash is None:
            device_hash = (
                os.environ.get("STELLAR_TPU_DEVICE_HASH", "0") == "1"
            )
        self.device_hash = bool(device_hash)
        if self.device_hash:
            from . import sha512 as _dsha

            self._rows = _dsha.DH_ROWS
        else:
            self._rows = 128
        # Host stage: the native C extension (gate + batch SHA-512 mod L +
        # packed staging with the GIL released — native/sighash.c) when it
        # builds, else the hashlib/numpy fallback.  native_hash=False (or
        # STELLAR_TPU_NATIVE_SIGHASH=0) pins the fallback for A/Bs.
        if native_hash is None:
            native_hash = (
                os.environ.get("STELLAR_TPU_NATIVE_SIGHASH", "1") != "0"
            )
        self._sighash = None
        if native_hash:
            from .. import native as _native

            self._sighash = _native.load_sighash()
        # 0 = auto (the C stage fans out over its pool for large chunks)
        try:
            self._hash_threads = int(
                os.environ.get("STELLAR_TPU_SIGHASH_THREADS", "0") or 0
            )
        except ValueError:
            self._hash_threads = 0
        self._pool = _StagingPool()
        if streams is None:
            streams = int(os.environ.get("STELLAR_TPU_VERIFY_STREAMS", "1"))
        if host_assist is None:
            try:
                host_assist = float(
                    os.environ.get("STELLAR_TPU_HOST_ASSIST", "0") or 0.0
                )
            except ValueError:
                host_assist = 0.0
        # Fraction of each large batch peeled off to a concurrent libsodium
        # loop: while device chunks upload/execute, the otherwise-idle host
        # core verifies the tail.  Worth cpu_rate/(cpu_rate+device_rate)
        # (~10-20%) of extra end-to-end throughput; results are identical
        # by construction (libsodium IS the ground truth the kernel is
        # differential-tested against).  0 disables.
        self.host_assist = min(0.9, max(0.0, host_assist))
        # dispatch streams: stager threads that stage+upload+launch chunks
        # concurrently.  1 = the classic pipeline (host prep of chunk k+1
        # overlaps device drain of chunk k).  2 = additionally overlap one
        # chunk's UPLOAD with another's EXECUTION — a win only if the
        # transfer pipelines with the kernel (bench A/Bs both and reports
        # the better)
        self.streams = max(1, streams)
        if backend == "auto":
            # pallas is a TPU (Mosaic) lowering: not CPU, and not GPU
            # either (interpret mode exists but is far slower than XLA)
            backend = "pallas" if jax.default_backend() == "tpu" else "xla"
        self.backend = backend
        # the Pallas kernel compiles with Mosaic only on a real TPU; on a
        # CPU mesh (tests, the driver dryrun) the same kernel runs in
        # interpreter mode — reported by stats() so a node can never
        # pass an interpreted kernel off as the device
        self.interpret = (
            backend == "pallas" and jax.default_backend() != "tpu"
        )
        n_shards = len(mesh.devices.flat) if mesh is not None else 1
        if self.backend == "pallas":
            from .ed25519_pallas import NT

            # every device batch must be a whole number of pallas tiles —
            # PER SHARD when a mesh splits the batch axis
            self._granule = NT * n_shards
        else:
            # every bucket must split evenly over the mesh's batch axis:
            # staging is one fixed-width buffer per shard, and a chunk
            # whose length is not divisible by n_shards pads the tail
            # shard (masked at drain — see _stage_chunk_sharded)
            self._granule = n_shards
        if self._granule > 1:
            self.max_batch = max(
                self._granule,
                -(-self.max_batch // self._granule) * self._granule,
            )
        # the kernel as jax.jit traces it, a trace a shape: what a bucket's
        # program is exported from, and what runs a bucket for which the
        # program store cannot be used
        self._kernel = self._make_kernel()
        # what a dispatch of a bucket calls, made once at the bucket's
        # first dispatch (_first_program) and kept: the jit of its stored
        # program, or self._kernel.  Never a new jit a dispatch: that would
        # trace the wrapper again at every flush
        self._calls: dict = {}  # analysis: locked-by _calls_lock
        # shared_programs: kernel and calls are the process's (above), and
        # a bucket another verifier of this process dispatched first is
        # warm here too, under that dispatch's record (_process_firsts)
        if shared_programs:
            key = (self.backend, self.interpret, self.device_hash, mesh)
            with _process_programs_lock:
                self._kernel, self._calls, self._process_firsts = (
                    _process_programs.setdefault(key, (self._kernel, {}, {}))
                )
        # buckets whose program has been loaded or lowered, and compiled,
        # in this process (one executable per padded batch size; layout,
        # mesh and lowering are fixed per verifier, and torsion proofs ride
        # the same program) — what cold_buckets() sizes a caller's
        # watchdog budget from
        self._warm_buckets: set = set()  # analysis: locked-by _calls_lock
        # what each bucket's first dispatch cost, by bucket, and the stage
        # events of dispatches after it: stats()["first_dispatch"]
        self._first_dispatches: dict = {}  # analysis: locked-by _calls_lock
        self._recompiles = StageTally()
        self.n_device_calls = 0
        self.n_lanes = 0
        self.n_items = 0
        self.n_gate_rejects = 0
        self.n_host_assist_items = 0
        self.n_torsion_items = 0
        # n_device_calls is bumped from every stager thread; += alone
        # drops increments under streams>1 and the counter feeds
        # profiling conclusions
        self._calls_lock = threading.Lock()

    def _make_kernel(self):
        """-> callable over the packed (128, N) — or, with device_hash,
        (160, N) — uint8 staging array.

        ONE host->device upload carries the whole chunk (A/R/s/h byte
        rows, or A/R/s/raw-M under device_hash); the row slicing, int32
        widening, nibble splitting — and with device_hash the whole
        SHA-512 mod L stage (ops/sha512.py) — all happen inside the jit
        program, so the host never touches the hash path for the
        dominant single-block class."""
        packed_fn = (
            _verify_packed_device_hash if self.device_hash else _verify_packed
        )
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as PSpec

            batch_axis = self.mesh.axis_names[0]
            shard = NamedSharding(self.mesh, PSpec(None, batch_axis))
            vec = NamedSharding(self.mesh, PSpec(batch_axis))
            # _upload_sharded assembles each chunk's per-shard staging
            # buffers under exactly this sharding, so the jit below never
            # inserts a reshard in front of the kernel
            self._shard_sharding = shard
            self._vec_sharding = vec
            if self.backend == "pallas":
                from jax import shard_map

                from .ed25519_pallas import verify_kernel_pallas

                interpret = self.interpret

                if self.device_hash:
                    from .sha512 import sha512_pallas

                    def body(p):
                        # the sha stage grids the same per-shard batch
                        # tiles, so both pallas_calls fuse into one jit
                        # with no cross-shard communication
                        h = sha512_pallas(p, interpret=interpret)
                        return verify_kernel_pallas(
                            p[0:32], p[32:64], p[64:96],
                            h.astype(jnp.uint8),
                            interpret=interpret,
                        )

                else:

                    def body(p):
                        return verify_kernel_pallas(
                            p[0:32], p[32:64], p[64:96], p[96:128],
                            interpret=interpret,
                        )

                fn = shard_map(
                    body,
                    mesh=self.mesh,
                    in_specs=(PSpec(None, batch_axis),),
                    out_specs=PSpec(batch_axis),
                    # pallas_call's out_shape carries no varying-mesh-axes
                    # annotation; the per-shard kernel is trivially
                    # batch-varying, so skip the VMA check
                    check_vma=False,
                )
                return jax.jit(fn, in_shardings=(shard,), out_shardings=vec)
            return jax.jit(
                partial(packed_fn, batch_inv=False),
                in_shardings=(shard,),
                out_shardings=vec,
            )
        if self.backend == "pallas":
            from .ed25519_pallas import verify_kernel_pallas

            interpret = self.interpret

            if self.device_hash:
                from .sha512 import sha512_pallas

                def packed_pallas(p):
                    h = sha512_pallas(p, interpret=interpret)
                    return verify_kernel_pallas(
                        p[0:32], p[32:64], p[64:96], h.astype(jnp.uint8),
                        interpret=interpret,
                    )

            else:

                def packed_pallas(p):
                    return verify_kernel_pallas(
                        p[0:32], p[32:64], p[64:96], p[96:128],
                        interpret=interpret,
                    )

            return jax.jit(packed_pallas)
        # unsharded batch axis: the lane-tree batched inversion is safe
        return jax.jit(partial(packed_fn, batch_inv=True))

    def _bucket(self, n: int) -> int:
        # _granule already folds the mesh width in (n_shards, or NT tiles
        # per shard for pallas), so every bucket splits evenly over chips
        b = max(self.min_device_batch, self._granule)
        b = -(-b // self._granule) * self._granule  # whole tiles per shard
        while b < n:
            b *= 2
        return min(b, self.max_batch) if n <= self.max_batch else self.max_batch

    def _host_assist_count(self, n: int) -> int:
        """Items of an n-item batch peeled onto the concurrent libsodium
        loop: only what exceeds a whole device granule, so small batches
        keep their single chunk."""
        if self.host_assist > 0.0 and n >= 4 * self._granule:
            return int(n * self.host_assist)
        return 0

    def _chunks(self, n_dev: int) -> List[Tuple[int, int]]:
        """(start, count) device chunk ranges over the first n_dev items."""
        return [
            (s, min(self.max_batch, n_dev - s))
            for s in range(0, n_dev, self.max_batch)
        ]

    def chunk_count(self, n: int, host_assist: bool = True) -> int:
        """How many device chunks a call over ``n`` items makes
        (``host_assist`` as for ``cold_buckets``)."""
        n_dev = n - self._host_assist_count(n) if host_assist else n
        return len(self._chunks(n_dev))

    def cold_buckets(self, n: int, host_assist: bool = True) -> int:
        """How many distinct buckets a call over ``n`` items dispatches to
        whose program has not run in this process yet.  Each costs, inside
        the call, the load of its stored program (``ops/programs.py``; the
        Python trace + lower where the machine has none yet) and a compile
        (on a persistent-cache hit, the read and load of the executable),
        so the caller's watchdog scales its budget by this count; what
        each cost this process is ``stats()["first_dispatch"]["buckets"]``.
        ``host_assist=False`` for torsion batches, which never peel."""
        n_dev = n - self._host_assist_count(n) if host_assist else n
        sizes = {self._bucket(count) for _, count in self._chunks(n_dev)}
        with self._calls_lock:
            return len(sizes - self._warm_buckets - set(self._process_firsts or ()))

    def verify(self, items: Sequence[Tuple[bytes, bytes, bytes]]) -> List[bool]:
        """items: (pubkey32, msg, sig64) triples -> list of bool.

        Chunks are (start, n) RANGES over ``items`` — no per-item tuple
        rebuild, no join/frombuffer of the whole batch: each chunk's gate
        + hash + staging happens in one C call over the original bytes
        objects (native/sighash.c), and gate verdicts mask the device
        results at drain time (a gate-rejected lane still occupies a
        device slot but can never report True)."""
        items = items if isinstance(items, (list, tuple)) else list(items)
        out = [False] * len(items)
        self.n_items += len(items)
        # Host-assist: peel the tail of a large batch onto a concurrent
        # libsodium loop (ctypes releases the GIL) so the host core works
        # while device chunks upload/execute.
        host_n = self._host_assist_count(len(items))
        n_dev = len(items) - host_n
        assist_join = None
        assist_err: List[BaseException] = []
        if host_n > 0:
            self.n_host_assist_items += host_n
            # _sodium_verify_loop pools over spare cores by itself —
            # the assist must not cap at one thread on the multi-core
            # hosts it exists for (r05 review)
            from ..crypto.sigbackend import _sodium_verify_loop

            def assist(start=n_dev, count=host_n):
                # a raise here must NOT die silently with the thread:
                # out[] rows would stay False and valid signatures
                # would be reported failed — capture and re-raise on
                # the caller after the join
                try:
                    with self._tracer.span(
                        "ed25519.host_assist", items=count
                    ):
                        oks = _sodium_verify_loop(
                            items[start : start + count]
                        )
                        for j, ok in enumerate(oks):
                            out[start + j] = ok
                except BaseException as e:
                    assist_err.append(e)

            _t = threading.Thread(
                target=assist, name="verify-host-assist", daemon=True
            )
            _t.start()
            assist_join = _t.join
        # Pipelined with bounded depth: a stager thread stages AND
        # dispatches chunk k+1 (the C host stage releases the GIL for the
        # whole gate+hash+staging pass) while the main thread blocks
        # draining chunk k-1 from the device; at most PIPELINE_DEPTH
        # chunks of device buffers are ever in flight (unbounded dispatch
        # could OOM the chip on huge replays).
        pending = []

        def drain_one():
            (start, n), staged, fut = pending.pop(0)
            dsp = self._tracer.begin("ed25519.drain")
            if fut is not None:
                out[start : start + n] = self._read_back(fut, staged, n)
            # fut None: every lane was gate-rejected — out[] rows stay
            # False without a device round-trip
            self._tracer.end(dsp, items=n)
            if staged is not None:
                self._pool.release(staged.bufs)

        try:
            self._run_pipeline(items, self._chunks(n_dev), pending, drain_one)
        finally:
            # join even when the device pipeline raises: an orphan assist
            # thread would compete with the caller's retry for host cores
            # (r05 review)
            if assist_join is not None:
                assist_join()
        if assist_err:
            # assist failure surfaces on the caller exactly like a device
            # failure would — after the join, so no orphan thread races a
            # retry for host cores
            raise assist_err[0]
        return out

    def verify_torsion(self, encs: Sequence[bytes]) -> List[bool]:
        """Batched prime-order-subgroup proofs on the SAME compiled
        verify kernel: [L]·P == identity is computed AS-IS via
        verify(A := P, h := L, s := 0, R := identity-encoding) — the
        ladder evaluates 0·B + L·(−P) and the byte compare against the
        identity encoding passes iff L·P is the identity (−identity ==
        identity).  No hash stage runs at all: the h column carries L
        directly, and under the device-hash layout the all-flag-0
        torsion chunk takes the sha stage's chunk-level lax.cond
        passthrough — the 80 rounds are skipped, not computed-and-
        discarded.

        This is the aggregate plane's fresh-R proof offload (ROADMAP #3
        remainder (a)): ~31 µs/point of host ``torsion_free`` becomes a
        device batch lane at ~the marginal verify cost, through the same
        mesh dispatch / staging-pool / drain machinery as verify().

        Input contract: ``encs`` are compressed point encodings.  A
        malformed length, non-canonical y, or undecodable encoding
        returns False (matching the host path, which strict-decodes
        first); callers on the aggregate plane only pass gated canonical
        encodings."""
        encs = encs if isinstance(encs, (list, tuple)) else list(encs)
        out = [False] * len(encs)
        if not encs:
            return out
        self.n_torsion_items += len(encs)
        pending = []

        def drain_one():
            (start, n), staged, fut = pending.pop(0)
            dsp = self._tracer.begin("ed25519.torsion_drain")
            if fut is not None:
                out[start : start + n] = self._read_back(fut, staged, n)
            self._tracer.end(dsp, items=n)
            if staged is not None:
                self._pool.release(staged.bufs)

        self._run_pipeline(
            encs,
            self._chunks(len(encs)),
            pending,
            drain_one,
            stage_fn=self._stage_torsion,
        )
        return out

    def _read_back(self, fut, staged: _Staged, n: int) -> List[bool]:
        """The two halves of a drain, as children that partition its span:
        the wait until the device's answer is ready, then the rest of the
        device -> host copy, the gate mask and the list.  The wait first
        queues the copy behind the kernel, as ``np.asarray`` on a pending
        result does: waiting and only then copying costs a host round trip
        a chunk (~120 us, my chip run, PR 24)."""
        with self._tracer.span("ed25519.wait"):
            jax.copy_to_host_async(fut)
            jax.block_until_ready(fut)
        with self._tracer.span("ed25519.readback"):
            return np.logical_and(np.asarray(fut)[:n], staged.ok[:n]).tolist()

    def _stage_torsion(self, encs, start, n) -> Optional[_Staged]:
        """Stage a torsion-proof chunk: A column = the encodings, R =
        identity encoding, s = 0, h = L (host-precomputed — no hash).
        Same pooled buffers / per-shard upload as the verify path."""
        if n == 0:
            return None
        if self.mesh is not None:
            n_shards = len(self.mesh.devices.flat)
            bucket = self._bucket(n)
            shard_bucket = bucket // n_shards
            bufs = []
            ok = np.empty(n, dtype=bool)
            for k in range(n_shards):
                pair = self._pool.acquire(shard_bucket, self._rows)
                bufs.append(pair)
                packed, okbuf = pair
                lo = k * shard_bucket
                cnt = min(shard_bucket, max(0, n - lo))
                if cnt == 0:
                    packed[:] = 0
                    continue
                self._fill_torsion(encs, start + lo, cnt, packed, okbuf)
                ok[lo : lo + cnt] = okbuf[:cnt].astype(bool)
            return _Staged([p for p, _ in bufs], ok, n, tuple(bufs))
        bucket = self._bucket(n)
        bufs = self._pool.acquire(bucket, self._rows)
        packed, okbuf = bufs
        self._fill_torsion(encs, start, n, packed, okbuf)
        return _Staged(packed, okbuf[:n].astype(bool), n, bufs)

    @staticmethod
    def _fill_torsion(encs, start, n, packed, okbuf) -> None:
        """numpy fill of one torsion chunk.  The device decompress does
        not re-check y-canonicity (the verify path's host gate does), so
        non-canonical encodings are gated right here to keep parity with
        the strict host decode."""
        from . import sha512 as dsha

        packed[:, :] = 0
        ok = np.zeros(n, dtype=bool)
        well = [j for j in range(n) if len(encs[start + j]) == 32]
        if well:
            enc_arr = np.frombuffer(
                b"".join(encs[start + j] for j in well), dtype=np.uint8
            ).reshape(-1, 32)
            # canonical y < 2^255 - 19 (sign bit masked) — the SAME
            # vectorized compare ref.strict_input_ok_batch runs, so the
            # torsion accept set has one implementation, not a twin
            enc_m = enc_arr.copy()
            enc_m[:, 31] &= 0x7F
            canon = ref._le_lt(enc_m.view("<u8").reshape(-1, 4), ref.P)
            idx = np.asarray(well, dtype=np.intp)
            ok[idx] = canon
            live = idx[canon]
            packed[0:32, live] = enc_arr[canon].T
        # R := identity encoding (0x01 ‖ 0^31), h := L, on live lanes only
        packed[32, :n] = ok
        packed[96:128, :n] = dsha.L_BYTES[:, None] * ok[None, :]
        okbuf[:n] = ok

    def _run_pipeline(self, items, chunks, pending, drain_one, stage_fn=None):
        stage = stage_fn if stage_fn is not None else self._stage_chunk
        if len(chunks) <= 1:
            for rng in chunks:
                staged = stage(items, *rng)
                pending.append((rng, staged, self._dispatch_staged(staged)))
            while pending:
                drain_one()
        else:
            from concurrent.futures import ThreadPoolExecutor

            # Bound SUBMITTED-but-undrained chunks at `depth`: a queued
            # future can start the moment a worker frees, so the
            # submission count is the device in-flight bound.  The bound
            # lives in a plain main-thread counter, NOT a semaphore
            # acquired on the workers — with streams>1 a later chunk's
            # worker could steal the last permit out of chunk order while
            # the main thread blocks on an earlier chunk's future that
            # can then never dispatch (deadlock, r05 review).  With >1
            # streams each needs an in-flight slot plus one being
            # drained, or the second stream can never overlap.
            depth = max(PIPELINE_DEPTH, self.streams + 1)
            # the stager threads' spans name the span open here (the
            # caller's flush) as their cause
            parent = self._tracer.current()
            # and serve the caller class this thread serves (the pool's
            # threads live for this call)
            caller = compile_events.serving()

            def stage_and_dispatch(rng):
                compile_events.serve(caller)
                with self._tracer.under(parent):
                    staged = stage(items, *rng)
                    return staged, self._dispatch_staged(staged)

            with ThreadPoolExecutor(max_workers=self.streams) as stager:
                futs = []
                drained = 0

                def drain_oldest():
                    nonlocal drained
                    rng, f = futs[drained]
                    drained += 1
                    staged, fut = f.result()
                    pending.append((rng, staged, fut))
                    drain_one()

                try:
                    for rng in chunks:
                        if len(futs) - drained >= depth:
                            drain_oldest()
                        futs.append(
                            (rng, stager.submit(stage_and_dispatch, rng))
                        )
                    while drained < len(futs):
                        drain_oldest()
                except BaseException:
                    # drop queued work; running workers just finish their
                    # chunk (nothing blocks on a lock), so executor
                    # __exit__ joins cleanly and the error propagates
                    for _, f in futs:
                        f.cancel()
                    raise

    def _stage_chunk(self, items, start, n) -> Optional[_Staged]:
        """Host stage over ``items[start:start+n]``: strict-input gate +
        h = SHA-512(R‖A‖M) mod L + the packed transposed (128, bucket)
        upload layout, into a pooled staging buffer.  The native C stage
        releases the GIL for the whole pass (and fans out over its
        internal thread pool on large chunks), so a stager thread running
        this genuinely overlaps device compute; the hashlib/numpy
        fallback covers toolchain-less hosts."""
        if n == 0:
            return None
        if self.mesh is not None:
            return self._stage_chunk_sharded(items, start, n)
        bucket = self._bucket(n)
        bufs = self._pool.acquire(bucket, self._rows)
        packed, okbuf = bufs
        sp = self._tracer.begin("ed25519.host_hash")
        rejects = self._stage_into(items, start, n, packed, okbuf)
        self._tracer.end(
            sp,
            items=n,
            native=self._sighash is not None,
            rejects=rejects,
            device_hash=self.device_hash,
        )
        if rejects:
            with self._calls_lock:  # stager threads update concurrently
                self.n_gate_rejects += int(rejects)
        return _Staged(packed, okbuf[:n].astype(bool), n, bufs)

    def _stage_chunk_sharded(self, items, start, n) -> _Staged:
        """Mesh staging: one pooled ``(128, bucket // n_shards)`` buffer
        PER SHARD, each filled by its own host-stage pass (the native C
        stage releases the GIL per call) and uploaded straight to its
        chip in _dispatch_staged — the global chunk is never repacked on
        host.  Live lanes occupy global columns [0, n) shard-major; a
        chunk not divisible by n_shards pads the tail shard and shards
        past the live range stage nothing (zeroed, inert lanes), so the
        drain's [:n] mask makes remainders bit-exact with the unsharded
        path."""
        n_shards = len(self.mesh.devices.flat)
        bucket = self._bucket(n)
        shard_bucket = bucket // n_shards
        bufs = []
        ok = np.empty(n, dtype=bool)
        rejects = 0
        sp = self._tracer.begin("ed25519.host_hash")
        for k in range(n_shards):
            pair = self._pool.acquire(shard_bucket, self._rows)
            bufs.append(pair)
            packed, okbuf = pair
            lo = k * shard_bucket
            cnt = min(shard_bucket, max(0, n - lo))
            if cnt == 0:
                packed[:] = 0  # dead shard: every lane is inert padding
                continue
            # under device_hash the per-chip pass drops its SHA stage:
            # gate + raw-byte packing only (the r16 lever — one full C
            # hash pass PER CHIP was the mesh's host feed bottleneck)
            rejects += self._stage_into(items, start + lo, cnt, packed, okbuf)
            ok[lo : lo + cnt] = okbuf[:cnt].astype(bool)
        self._tracer.end(
            sp,
            items=n,
            native=self._sighash is not None,
            rejects=rejects,
            shards=n_shards,
            device_hash=self.device_hash,
        )
        if rejects:
            with self._calls_lock:  # stager threads update concurrently
                self.n_gate_rejects += int(rejects)
        return _Staged([p for p, _ in bufs], ok, n, tuple(bufs))

    def _stage_into(self, items, start, n, packed, okbuf) -> int:
        """One host-stage pass into a pooled buffer: the C extension when
        it built (GIL released for the whole pass), else the Python
        fallback — routed by layout.  Host-hash: gate + SHA-512 mod L +
        (128, ·) staging.  Device-hash: gate + raw-byte (160, ·) staging."""
        if self._sighash is None:
            stage_py = self._stage_py_raw if self.device_hash else self._stage_py
            return stage_py(items, start, n, packed, okbuf)
        stage = self._sighash.stage_raw if self.device_hash else self._sighash.stage
        return stage(
            items, start, n, packed, okbuf, _BLACKLIST, self._hash_threads
        )

    def _stage_py_raw(self, items, start, n, packed, okbuf) -> int:
        """Pure-Python device-hash staging (numpy gate + raw-byte pack;
        hashlib only for the multi-block residual class) filling the
        (160, ·) layout — the no-toolchain fallback twin of native
        stage_raw."""
        from . import sha512 as dsha

        chunk = [items[start + j] for j in range(n)]
        ok = np.zeros(n, dtype=bool)
        well = [
            j
            for j, it in enumerate(chunk)
            if len(it[-3]) == 32 and len(it[-1]) == 64
        ]
        packed[:, :n] = 0
        if well:
            pk_arr = np.frombuffer(
                b"".join(chunk[j][-3] for j in well), dtype=np.uint8
            ).reshape(-1, 32)
            sig_arr = np.frombuffer(
                b"".join(chunk[j][-1] for j in well), dtype=np.uint8
            ).reshape(-1, 64)
            gate = ref.strict_input_ok_batch(pk_arr, sig_arr)
            sha = hashlib.sha512
            for k, j in enumerate(well):
                if not gate[k]:
                    continue
                ok[j] = True
                pk, msg, sig = chunk[j][-3], chunk[j][-2], chunk[j][-1]
                packed[0:32, j] = pk_arr[k]
                packed[32:64, j] = sig_arr[k, :32]
                packed[64:96, j] = sig_arr[k, 32:]
                if len(msg) <= dsha.MAX_DEVICE_MSG:
                    if msg:
                        packed[96 : 96 + len(msg), j] = np.frombuffer(
                            msg, dtype=np.uint8
                        )
                    packed[dsha.ROW_MLEN, j] = len(msg)
                    packed[dsha.ROW_FLAG, j] = 1
                else:
                    h = (
                        int.from_bytes(
                            sha(sig[:32] + pk + msg).digest(), "little"
                        )
                        % L
                    )
                    packed[96:128, j] = np.frombuffer(
                        h.to_bytes(32, "little"), dtype=np.uint8
                    )
        packed[:, n:] = 0
        okbuf[:n] = ok
        return n - int(ok.sum())

    def _stage_py(self, items, start, n, packed, okbuf) -> int:
        """Pure-Python host stage (hashlib + the vectorized numpy gate)
        filling the same packed layout — the pre-native code path, kept
        as the no-toolchain fallback and the bench A/B baseline."""
        chunk = [items[start + j] for j in range(n)]
        ok = np.zeros(n, dtype=bool)
        well = [
            j
            for j, it in enumerate(chunk)
            if len(it[-3]) == 32 and len(it[-1]) == 64
        ]
        packed[:, :n] = 0
        if well:
            pk_arr = np.frombuffer(
                b"".join(chunk[j][-3] for j in well), dtype=np.uint8
            ).reshape(-1, 32)
            sig_arr = np.frombuffer(
                b"".join(chunk[j][-1] for j in well), dtype=np.uint8
            ).reshape(-1, 64)
            gate = ref.strict_input_ok_batch(pk_arr, sig_arr)
            sha = hashlib.sha512
            for k, j in enumerate(well):
                if not gate[k]:
                    continue
                ok[j] = True
                pk, msg, sig = chunk[j][-3], chunk[j][-2], chunk[j][-1]
                packed[0:32, j] = pk_arr[k]
                packed[32:64, j] = sig_arr[k, :32]
                packed[64:96, j] = sig_arr[k, 32:]
                h = (
                    int.from_bytes(
                        sha(sig[:32] + pk + msg).digest(), "little"
                    )
                    % L
                )
                packed[96:128, j] = np.frombuffer(
                    h.to_bytes(32, "little"), dtype=np.uint8
                )
        packed[:, n:] = 0
        okbuf[:n] = ok
        return n - int(ok.sum())

    def _dispatch_staged(self, staged: Optional[_Staged]):
        """Upload the packed staging buffer (ONE transfer) and launch the
        kernel.  Runs on the stager thread in the multi-chunk pipeline,
        on the caller's thread for single-chunk batches.  Returns the
        in-flight device result, or None when every lane was
        gate-rejected (hostile floods never reach the chip).

        A bucket's first dispatch in this process loads its lowered
        program from the program store — or traces and lowers it, and
        stores it — and compiles it (``_first_program``): the thread opens
        an account for what JAX reports of that, and the record goes to
        ``stats()``, onto this one span and into one log line.  A later
        dispatch marks its thread too, so that a compilation that should
        not happen any more is counted against its bucket."""
        if staged is None or not staged.ok.any():
            return None
        dsp = self._tracer.begin("ed25519.device_dispatch")
        if self.mesh is not None:
            bucket = sum(buf.shape[1] for buf in staged.packed)
        else:
            bucket = staged.packed.shape[1]
        with self._calls_lock:
            cold = bucket not in self._warm_buckets
            if cold and self._process_firsts is not None:
                paid = self._process_firsts.get(bucket)
                if paid is not None:
                    # another verifier of this process paid for the bucket
                    self._warm_buckets.add(bucket)
                    self._first_dispatches[bucket] = paid
                    cold = False
            call = None if cold else self._calls[bucket]
        account = (
            _FirstDispatch(bucket, compile_events.serving())
            if cold
            else self._recompiles
        )
        compile_events.charge(account, bucket)
        try:
            if cold:
                call = self._first_program(bucket, account)
            if self.mesh is not None:
                arr = self._upload_sharded(staged.packed)
            else:
                arr = jnp.asarray(staged.packed)
            # returns once the program is compiled and the execution enqueued
            if cold and call is not self._kernel:
                ok = self._first_call(bucket, account, call, arr)
            else:
                ok = call(arr)
        finally:
            compile_events.charge(None)
        attrs = self._note_first_dispatch(account.close()) if cold else {}
        self._tracer.end(dsp, bucket=bucket, backend=self.backend, **attrs)
        with self._calls_lock:
            self.n_device_calls += 1
            self.n_lanes += bucket
        return ok

    def _program_fields(self, bucket: int) -> dict:
        """Everything that decides the program a bucket lowers to, and
        nothing that does not (``ops/programs.py``): a stale program is a
        wrong verdict, so where in doubt a field is in."""
        import jaxlib

        dev = jax.devices()[0]
        fields = {
            "sources": programs.source_digests(),
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            # libtpu's build is in it
            "platform_version": dev.client.platform_version,
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "device_count": len(jax.devices()),
            "mesh": (
                None
                if self.mesh is None
                else [list(self.mesh.axis_names), list(self.mesh.devices.shape)]
            ),
            "x64": bool(jax.config.jax_enable_x64),
            "bucket": bucket,
            "rows": self._rows,
            "backend": self.backend,
            "interpret": self.interpret,
            "device_hash": self.device_hash,
        }
        # the module-level flags that change the traced body, by value
        if self.backend == "pallas":
            from . import ed25519_pallas as pallas

            fields.update(
                NT=pallas.NT,
                batch_inv=pallas._BATCH_INV,
                signed_win=pallas._SIGNED_WIN,
            )
        else:
            fields["batch_inv"] = self.mesh is None
        return fields

    def _first_program(self, bucket: int, account: _FirstDispatch):
        """-> what this verifier calls for ``bucket`` from now on.  A hit
        of the program store deserialises the bucket's program; a miss
        exports it from ``self._kernel`` — the one trace and lowering this
        machine pays for the bucket — stores it, and runs through the
        stored program on this process too, so that the executable the
        persistent cache keeps is the one every later process asks for.
        Whatever goes wrong leaves the bucket on ``self._kernel``, is
        logged once and counted (``programs_traced``); it never fails a
        flush, and nothing is tried again for the bucket in this process."""
        t0 = time.monotonic()
        try:
            directory = programs.store_dir()
            if directory is None:
                raise FileNotFoundError("no directory for the program store")
            path = account.program_path = programs.path_of(
                directory, self._program_fields(bucket)
            )
            try:
                exported = programs.load(path)
            finally:
                account.program_load_s = time.monotonic() - t0
            if exported is not None:
                account.program = PROGRAM_STORED
            else:
                if not os.access(directory, os.W_OK):
                    # asked before the export, not found out at the write:
                    # the trace and the lowering would be paid twice
                    raise PermissionError(directory)
                seen, t1 = account.trace_lower_s(), time.monotonic()
                traced = jax.export.export(self._kernel)(
                    jax.ShapeDtypeStruct((self._rows, bucket), jnp.uint8)
                )
                if account.trace_lower_s() <= seen:
                    # a JAX that reports no stage from inside the export:
                    # the call's own time, or the account would go blind
                    # on the one path that still costs a minute
                    account.add("trace_s", time.monotonic() - t1, bucket)
                exported = programs.save(path, traced)
                account.program = PROGRAM_EXPORTED
            if self.mesh is not None:
                call = jax.jit(
                    exported.call,
                    in_shardings=(self._shard_sharding,),
                    out_shardings=self._vec_sharding,
                )
            else:
                call = jax.jit(exported.call)
        except Exception as e:
            call = self._program_unusable(bucket, account, e)
        with self._calls_lock:
            # of two threads at one cold bucket both run what the first kept
            return self._calls.setdefault(bucket, call)

    def _program_unusable(self, bucket, account, err):
        """The program store failed ``bucket``: remove the file where there
        is one, say so once, and leave the bucket on the traced kernel."""
        if account.program_path is not None:
            programs.discard(account.program_path)
        account.program = PROGRAM_TRACED
        account.program_error = type(err).__name__
        _log.warning(
            "bucket %d: no stored program (%s: %s); tracing the kernel",
            bucket,
            type(err).__name__,
            err,
        )
        return self._kernel

    def _first_call(self, bucket, account, call, arr):
        """A stored program's first call, where it is lowered into its
        wrapper and compiled: a module that does not parse or a program
        that refuses the platform or the device count shows here."""
        try:
            return call(arr)
        except Exception as e:
            with self._calls_lock:
                self._calls[bucket] = self._program_unusable(bucket, account, e)
            return self._kernel(arr)

    def _note_first_dispatch(self, rec: dict) -> dict:
        """Keep and log the record of a bucket's first dispatch; returns
        what of it the dispatch's span carries (nothing for the loser of
        two threads that dispatched one cold bucket at once)."""
        bucket = rec["bucket"]
        with self._calls_lock:
            self._warm_buckets.add(bucket)
            if self._first_dispatches.setdefault(bucket, rec) is not rec:
                return {}
            if self._process_firsts is not None:
                self._process_firsts.setdefault(bucket, rec)
        saved = rec.get("compile_time_saved_s")
        _log.info(
            "bucket %d first dispatch %.1f s: program %s, trace %.1f,"
            " lower %.1f, compile %.1f (cache %s%s), rest %.1f; caller %s",
            bucket,
            rec["end"] - rec["start"],
            rec["program"],
            rec["trace_s"],
            rec["lower_s"],
            rec["compile_s"],
            rec["cache"],
            "" if saved is None else ", %.1f s saved" % saved,
            rec["rest_s"],
            rec["caller"],
        )
        attrs = {k: rec[k] for k in _FIRST_SPAN_ATTRS if k in rec}
        attrs["first"] = True
        return attrs

    def _upload_sharded(self, shards):
        """One host->device transfer PER SHARD: each chip's C-contiguous
        staging buffer goes straight to that chip, and the global chunk
        array is assembled from the single-device pieces under the exact
        sharding the jitted kernel expects — XLA inserts no reshard, so
        the only collective in the whole round-trip is the (N,) bool
        output all-gather the drain joins."""
        devices = list(self.mesh.devices.flat)
        singles = [
            jax.device_put(buf, dev) for buf, dev in zip(shards, devices)
        ]
        bucket = sum(buf.shape[1] for buf in shards)
        return jax.make_array_from_single_device_arrays(
            (self._rows, bucket), self._shard_sharding, singles
        )

    def stats(self) -> dict:
        # gate_rejects counts the device pipeline's strict-gate verdicts
        # (malformed lengths included); host-assist items go through
        # libsodium whole and are not broken out
        dev = jax.devices()[0]
        return {
            "backend": "tpu",
            # what actually runs the kernel: the device as JAX reports it
            # and the lowering ("pallas" compiled by Mosaic, "pallas" with
            # interpret true, or "xla")
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "device_count": len(jax.devices()),
            "kernel": self.backend,
            "interpret": self.interpret,
            "device_calls": self.n_device_calls,
            "items": self.n_items,
            # sum of the bucket sizes dispatched: items / lanes is how full
            # the device's lanes were (5,000 items ride 4096 + 1024)
            "lanes": self.n_lanes,
            "gate_rejects": self.n_gate_rejects,
            "host_assist_items": self.n_host_assist_items,
            "native_host_stage": self._sighash is not None,
            # device-resident SHA-512 stage (ops/sha512.py): True = the
            # host keeps only the strict gate for single-block preimages
            "device_hash": self.device_hash,
            # [L]·P == identity proofs served on the batch plane (the
            # aggregate scheme's fresh-R offload)
            "torsion_items": self.n_torsion_items,
            "first_dispatch": self._first_dispatch_stats(),
            # 0 = unsharded single-queue dispatch; >0 = chips on the
            # batch-axis mesh (Config.SIG_MESH; bench close lines carry
            # this as sig_mesh_devices so every JSON records the mode)
            "mesh_devices": (
                len(self.mesh.devices.flat) if self.mesh is not None else 0
            ),
        }

    def _first_dispatch_stats(self) -> dict:
        """Where the seconds of each bucket's first dispatch went, as JAX
        reported them on the dispatching thread (counted whether or not
        the tracer is on; monotonic).  ``wall_s`` is the length of the
        union of the records' intervals: two buckets first dispatched on
        two threads interleave under the interpreter lock, and their sum
        would count the overlap twice.  ``unattributed``: stage events of
        the whole process that no dispatch was open for; ``recompiles``:
        those of a dispatch whose bucket had run before — 0 on a healthy
        node, whatever its age."""
        with self._calls_lock:
            recs = {b: dict(r) for b, r in self._first_dispatches.items()}
        out: dict = {
            "buckets": recs,
            "wall_s": _union_seconds(
                (r["start"], r["end"]) for r in recs.values()
            ),
        }
        for k in _FIRST_SUMS:
            out[k] = sum(r[k] for r in recs.values())
        # how often the program store engages
        for kind in (PROGRAM_STORED, PROGRAM_EXPORTED, PROGRAM_TRACED):
            out["programs_" + kind] = sum(
                1 for r in recs.values() if r["program"] == kind
            )
        loose = compile_events.unattributed.stats()
        out["unattributed"] = {k: loose[k] for k in ("events", "seconds")}
        out["recompiles"] = self._recompiles.stats()
        return out
