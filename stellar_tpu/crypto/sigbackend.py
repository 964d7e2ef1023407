"""SigBackend — the batched signature-verification abstraction.

This is the north-star design point of the framework (BASELINE.json): the
reference calls libsodium inline at three sites (SURVEY.md §2.8); here every
verify is expressed as a *batch* of (pubkey, msg, sig) triples so the hot
paths (TxSetFrame.check_valid, Herder.verify_envelope, ledger close) can
flush hundreds-to-thousands of verifies at once onto the TPU.

Selected via config ``SIGNATURE_BACKEND = "cpu" | "tpu"`` (the reference has
no such knob; its equivalent is the hardwired libsodium call at
SecretKey.cpp:277-279).  Both backends sit behind the same global verify
cache, so eager single verifies (PubKeyUtils.verify_sig) and batch verifies
share memoization exactly like the reference's gVerifySigCache.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Iterable, List, Optional, Sequence, Tuple

from ..trace import NULL_TRACER
from ..util import xlog
from . import sodium
from .sigcache import VerifySigCache

_log = xlog.logger("Tx")

VerifyTriple = Tuple[bytes, bytes, bytes]  # (pubkey32, msg, sig64)

# Caller classes for the tpu backend's host-fallback latch (and the async
# flush plane's attribution): a stalled PIPELINED prewarm must never route
# subsequent SYNCHRONOUS close-path batches onto host — the latch is scoped
# per class (ISSUE r10 satellite; see TpuSigBackend.verify_batch).
CALLER_CLOSE = "close"        # synchronous close-path / check_valid flushes
CALLER_PIPELINE = "pipeline"  # close-pipeline async prewarms (ledger N+1)
CALLER_OVERLAY = "overlay"    # per-crank SCP envelope batch flushes
CALLER_INGEST = "ingest"      # tx admission-plane micro-batches (front door)


class SigFlushFuture:
    """Handle to one in-flight asynchronous batch verify — the unit the
    close-pipeline scheduler dispatches while ledger N applies and joins at
    the top of ledger N+1's close.

    Lifecycle: ``dispatch`` (worker starts) → ``complete`` (verdicts ready;
    a caching backend latches them into the shared verify cache at this
    point, never earlier) → ``result()`` (join; re-raises a worker error).
    ``quarantine()`` severs the future from the cache plane: verdicts from
    a quarantined batch are never latched, and any already latched are
    evicted — an aborted/forked close must not leave its in-flight flush's
    writes behind (the contract tests/test_closepipeline.py pins).

    Timestamps (``time.monotonic``) let the scheduler account overlap:
    ``completed_at - dispatched_at`` is the async verify's duration; the
    part of it that elapsed before the join is hidden work."""

    def __init__(self, n_items: int):
        self.items = n_items
        self.dispatched_at = time.monotonic()
        self.completed_at: Optional[float] = None
        self._done = threading.Event()
        self._lock = threading.Lock()
        self._result: Optional[List[bool]] = None
        self._err: Optional[BaseException] = None
        self._quarantined = False  # analysis: locked-by _lock
        # set by CachingSigBackend before dispatch: (cache, [(key, idx)...])
        # mapping miss keys to result rows — the latch happens inside
        # _complete under the future's lock so quarantine() can never race
        # a put_many it doesn't see
        self._latch = None  # analysis: locked-by _lock
        self._latched = False  # analysis: locked-by _lock
        # the close pipeline's: set at the first join of a flush that
        # several ledgers' sets rode, so its hidden work is counted once
        self.joined = False

    def done(self) -> bool:
        return self._done.is_set()

    def quarantined(self) -> bool:
        with self._lock:
            return self._quarantined

    def quarantine(self) -> None:
        """Disown the batch: results will not (and no longer do) back the
        shared verify cache.  Idempotent; safe in any state."""
        with self._lock:
            self._quarantined = True
            if self._latched and self._latch is not None:
                cache, key_rows = self._latch
                cache.drop_many(k for k, _ in key_rows)
                self._latched = False

    def _complete(self, result=None, err=None) -> None:
        with self._lock:
            self.completed_at = time.monotonic()
            if err is not None:
                self._err = err
            else:
                self._result = result
                if self._latch is not None and not self._quarantined:
                    cache, key_rows = self._latch
                    # valid verdicts only, mirroring the synchronous path:
                    # the shared cache never holds an invalid-sig verdict
                    # (flood cache-pollution defense)
                    cache.put_many(
                        (k, result[i]) for k, i in key_rows if result[i]
                    )
                    self._latched = True
        self._done.set()

    def result(self, timeout: Optional[float] = None) -> List[bool]:
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"sig-flush future ({self.items} items) not done in {timeout}s"
            )
        with self._lock:
            if self._quarantined:
                raise RuntimeError("sig-flush future was quarantined")
            if self._err is not None:
                raise self._err
            return self._result

# Default device/host breakeven for the tpu backend, in cache-miss verifies:
# n/host_rate = dispatch_latency + n/device_rate.  The value is NOT measured
# on a locally-attached chip (it predates one; ROADMAP S3 replaces it with a
# dispatch-latency measurement) — retune HERE (Config.TPU_CPU_CUTOVER
# references this constant).
DEFAULT_TPU_CPU_CUTOVER = 1024


class SigBackend:
    name = "abstract"

    def verify_batch(
        self, items: Sequence[VerifyTriple], caller: str = CALLER_CLOSE
    ) -> List[bool]:
        raise NotImplementedError

    def torsion_check(
        self,
        encs: Sequence[bytes],
        caller: str = CALLER_OVERLAY,
        vals: Optional[Sequence] = None,
    ) -> List[bool]:
        """Batched prime-order-subgroup proofs ([L]·P == identity) over
        compressed point encodings — the aggregate plane's fresh-R proof
        surface (ROADMAP #3 remainder (a)).  True iff the encoding is a
        canonical, decodable, torsion-free point.  The base
        implementation strict-decodes + proves on host
        (native/halfagg.c's ladder or the ref25519 oracle); the tpu
        backend overrides with the device batch plane, same
        cutover/wedge-latch contracts as verify_batch.  ``vals`` —
        optional decoded points parallel to ``encs`` (what the aggregate
        plane's _decompress_many already produced): the host path proves
        them directly instead of re-decoding the encodings."""
        from ..crypto.aggregate import halfagg

        if vals is not None:
            return halfagg.torsion_free_points(vals)
        return halfagg.torsion_free_encs(encs)

    def verify_batch_async(
        self, items: Sequence[VerifyTriple], caller: str = CALLER_PIPELINE
    ) -> SigFlushFuture:
        """Dispatch the batch on a worker thread and return a future over
        it — the stage/drain split promoted to the backend surface, so a
        caller (ledger close, bench's deferred-flush leg) can overlap the
        verify with its own host work and join later.  Uncached backends
        just run verify_batch off-thread; CachingSigBackend adds the
        peek/latch split (and the quarantine contract) on top."""
        fut = SigFlushFuture(len(items))

        def work():
            try:
                fut._complete(result=self.verify_batch(items, caller=caller))
            except BaseException as e:  # re-raised at fut.result()
                fut._complete(err=e)

        threading.Thread(target=work, name="sig-flush", daemon=True).start()
        return fut

    def stats(self) -> dict:
        return {"backend": self.name}


class CachingSigBackend(SigBackend):
    """Wraps an inner backend with the shared verify cache: cached results
    are served immediately, only misses reach the inner backend, and results
    scatter back into the cache."""

    def __init__(self, inner: SigBackend, cache: VerifySigCache, tracer=None):
        self.inner = inner
        self.cache = cache
        self.name = inner.name
        self._tracer = tracer if tracer is not None else NULL_TRACER

    def verify_batch(
        self, items: Sequence[VerifyTriple], caller: str = CALLER_CLOSE
    ) -> List[bool]:
        # one sig-flush span per batch (never per item): batch size and the
        # cache-hit/miss split are THE attribution the close trace needs
        with self._tracer.span("sig.flush") as sp:
            keys = [self.cache.key_for(pk, sig, msg) for pk, msg, sig in items]
            cached = self.cache.peek_many(keys)
            miss_idx = [i for i, c in enumerate(cached) if c is None]
            if miss_idx:
                fresh = self.inner.verify_batch(
                    [items[i] for i in miss_idx], caller=caller
                )
                # latch VALID verdicts only: a byzantine flood of distinct
                # invalid-sig items must not be able to evict honest entries
                # from the bounded LRU (cache-pollution defense; re-verifying
                # an invalid item is cheap and pure, so nothing is lost) —
                # the chaos plane's flood scenarios pin this contract
                self.cache.put_many(
                    (keys[i], ok) for i, ok in zip(miss_idx, fresh) if ok
                )
                for i, ok in zip(miss_idx, fresh):
                    cached[i] = ok
            self._tracer.end(
                sp,
                batch=len(items),
                cache_hits=len(items) - len(miss_idx),
                misses=len(miss_idx),
                backend=self.name,
            )
        return [bool(c) for c in cached]

    def verify_batch_async(
        self, items: Sequence[VerifyTriple], caller: str = CALLER_PIPELINE
    ) -> SigFlushFuture:
        """The async flush over the peek/verify/latch split, ENTIRELY on
        the worker: key hashing, the cache peek, the miss verify, and the
        at-completion scatter-back all run off the caller's thread — the
        dispatching close overlaps every pure-compute part of the flush
        with its own host work (the caller only pays the list snapshot +
        thread spawn).  The latch rides the future, so a quarantined
        (aborted-close) batch can never leave verdicts behind."""
        items = list(items)
        fut = SigFlushFuture(len(items))
        # the worker's spans name the span open here as their cause (the
        # close pipeline's dispatch, or the close's own sig flush)
        tracer = self._tracer
        parent = tracer.current()

        def work():
            try:
                with tracer.under(parent):
                    sp = tracer.begin("sig.flush_async")
                    keys = [
                        self.cache.key_for(pk, sig, msg) for pk, msg, sig in items
                    ]
                    cached = self.cache.peek_many(keys)
                    miss_idx = [i for i, c in enumerate(cached) if c is None]
                    tracer.end(
                        sp,
                        batch=len(items),
                        cache_hits=len(items) - len(miss_idx),
                        misses=len(miss_idx),
                        backend=self.name,
                    )
                    if not miss_idx:
                        fut._complete(result=[bool(c) for c in cached])
                        return
                    # plain attribute store is atomic; _complete reads it
                    # under fut._lock and skips the latch if a quarantine won
                    # analysis: off locked-field -- happens-before by program order on the worker: _latch is written before the inner verify_batch, and _complete (same thread, after it) is the only reader path — there is no concurrent writer to exclude
                    fut._latch = (self.cache, [(keys[i], i) for i in miss_idx])
                    fresh = self.inner.verify_batch(
                        [items[i] for i in miss_idx], caller=caller
                    )
                    merged = list(cached)
                    for i, ok in zip(miss_idx, fresh):
                        merged[i] = ok
                    fut._complete(result=[bool(c) for c in merged])
            except BaseException as e:  # re-raised at fut.result()
                fut._complete(err=e)

        threading.Thread(target=work, name="sig-flush", daemon=True).start()
        return fut

    def torsion_check(
        self,
        encs: Sequence[bytes],
        caller: str = CALLER_OVERLAY,
        vals: Optional[Sequence] = None,
    ) -> List[bool]:
        # no verdict caching here: point-level memoization lives in the
        # aggregate plane's PointCache (keyed by encoding, where the
        # proof is intrinsic), not the signature verify cache
        return self.inner.torsion_check(encs, caller=caller, vals=vals)

    def stats(self) -> dict:
        s = self.inner.stats()
        # verifications that bypassed every batch path: cache misses of the
        # eager ``PubKeyUtils.verify_sig``, done by libsodium one at a time
        s["eager_host_verifies"] = self.cache.eager_host_verifies
        return s


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name)
    if raw:
        try:
            return float(raw)
        except ValueError:
            _log.warning("ignoring malformed %s=%r; using %s", name, raw, default)
    return default


_pool = None
_pool_lock = threading.Lock()
# threads a large host batch fans out over (_sodium_verify_loop)
_SODIUM_WORKERS = min(8, os.cpu_count() or 1)


def _sodium_verify_native(items: Sequence[VerifyTriple]) -> Optional[List[bool]]:
    """Fan a whole cache-miss batch over the native sighash worker pool:
    ONE GIL-released C call whose tiles invoke libsodium's
    crypto_sign_verify_detached through a function pointer (resolved from
    the SAME loaded library the serial path calls), so multi-core hosts
    parallelize the strict-verify leg with zero per-item Python dispatch
    — the Python ThreadPoolExecutor fallback below still serializes the
    per-chunk loop bookkeeping under the GIL.

    Returns None when the extension, libsodium, or the bytes-only item
    contract is unavailable; the caller falls back.  Verdicts are
    byte-identical to sodium.verify_detached (the C tile mirrors its
    length prechecks, then calls the same function)."""
    from ..native import load_sighash

    mod = load_sighash()
    if mod is None:
        return None
    try:
        fn = sodium.verify_fn_addr()
    except RuntimeError:
        return None
    ok = bytearray(len(items))
    try:
        mod.sodium_verify(fn, items, ok)
    except TypeError:
        # a non-bytes buffer slipped into the batch (the C side borrows
        # pointers across the GIL release, so it accepts bytes only) —
        # the Python loop handles such items fine
        return None
    return [bool(b) for b in ok]


def _sodium_verify_loop(items: Sequence[VerifyTriple]) -> List[bool]:
    """One libsodium verify per triple — the reference's exact behavior
    (crypto_sign_verify_detached, SecretKey.cpp:277-279).  Shared by the
    cpu backend and the tpu backend's small-batch cutover.

    Large batches fan out over the native sighash pthread pool when the
    extension built (one GIL-released C call, see _sodium_verify_native),
    else over a Python thread pool (the ctypes call releases the GIL, so
    it still scales, minus the per-chunk Python overhead).  Single-core
    hosts and small batches keep the plain serial loop — byte-identical
    to the reference, per the r09 satellite contract."""
    n = len(items)
    workers = _SODIUM_WORKERS
    if n < 256 or workers < 2:
        return [sodium.verify_detached(sig, msg, pk) for pk, msg, sig in items]
    native = _sodium_verify_native(items)
    if native is not None:
        return native
    global _pool
    if _pool is None:
        from concurrent.futures import ThreadPoolExecutor

        with _pool_lock:  # e.g. prewarm worker + main thread racing init
            if _pool is None:
                _pool = ThreadPoolExecutor(
                    max_workers=workers, thread_name_prefix="sodium-verify"
                )
    chunk = (n + workers - 1) // workers

    def run(lo):
        return [
            sodium.verify_detached(sig, msg, pk)
            for pk, msg, sig in items[lo : lo + chunk]
        ]

    parts = list(_pool.map(run, range(0, n, chunk)))
    return [ok for part in parts for ok in part]


class CpuSigBackend(SigBackend):
    name = "cpu"

    def verify_batch(
        self, items: Sequence[VerifyTriple], caller: str = CALLER_CLOSE
    ) -> List[bool]:
        return _sodium_verify_loop(items)


class TpuSigBackend(SigBackend):
    """JAX batched ed25519 verify: strict canonicity/small-order prechecks and
    SHA-512 reduction on host, curve math (decompress + double-scalar-mult)
    on the accelerator.  Bit-exact with libsodium by construction + the
    differential test suite (tests/test_ed25519_tpu.py)."""

    name = "tpu"
    # class-level defaults: harness code (and tests) that build the backend
    # via __new__ + hand-set attributes still get a working no-op tracer
    # and a flush count of their own
    _tracer = NULL_TRACER
    n_device_flushes = 0
    n_caller_items = None
    # libsodium's own seconds (stats() "host_verify"): batches, items and
    # the time inside _sodium_verify_loop wherever this backend verifies on
    # the host — taken inside the ``sig.host_verify`` span, so none of the
    # tracer is in them (and counted with the tracer off too)
    n_host_verify_calls = 0
    n_host_verify_items = 0
    host_verify_s = 0.0

    def __init__(
        self,
        max_batch: int = 4096,
        mesh=None,
        sig_mesh=0,
        cpu_cutover: int = DEFAULT_TPU_CPU_CUTOVER,
        streams: int = 1,
        native_hash: bool = True,
        device_hash: bool = False,
        tracer=None,
        shared_programs: bool = False,
    ):
        from ..ops.verifier import BatchVerifier  # lazy: JAX import

        self._tracer = tracer if tracer is not None else NULL_TRACER
        # sig_mesh: the Config.SIG_MESH production wiring — 0/off,
        # "auto" (all addressable chips), or an explicit device count;
        # an explicit ``mesh=`` object (tests, the dryrun harness) wins.
        # Sharded dispatch rides the same BatchVerifier surface, so every
        # caller class (close flush, pipeline prewarms, overlay batches)
        # and the wedge-latch/quarantine contracts inherit it unchanged.
        if mesh is None and sig_mesh:
            from ..parallel.mesh import mesh_from_spec

            mesh = mesh_from_spec(sig_mesh)
        # native_hash: the C host stage (gate + batch SHA-512 mod L,
        # native/sighash.c) — on when it builds; stats() reports which
        # stage is live as "native_host_stage".
        # device_hash: the Config.DEVICE_HASH production wiring — the
        # SHA-512 stage runs ON DEVICE fused ahead of the verify kernel
        # (ops/sha512.py) and the host keeps only the strict gate.
        self._verifier = BatchVerifier(
            max_batch=max_batch,
            mesh=mesh,
            streams=streams,
            native_hash=native_hash,
            device_hash=device_hash,
            tracer=tracer,
            shared_programs=shared_programs,
        )
        # Below this many cache misses a device round-trip costs more than
        # looping libsodium on host — lone SCP envelopes and small tx sets
        # must never pay device latency just because the backend is "tpu"
        # (see DEFAULT_TPU_CPU_CUTOVER for the breakeven arithmetic).
        self.cpu_cutover = cpu_cutover
        self.n_cutover_items = 0
        self.n_cutover_torsion = 0
        # verify items by caller class and by where they were verified
        # (stats() "caller_items"): which plane the device serves
        self.n_caller_items = {}
        self.n_wedge_fallback_items = 0
        # Host-fallback latch, scoped PER CALLER CLASS (ISSUE r10): a
        # stalled pipelined prewarm (caller="pipeline") latches only the
        # pipeline plane — the synchronous close-path batches
        # (caller="close") keep probing the device, and vice versa.  A
        # single shared latch silently routed every subsequent close flush
        # onto host for RETRY_INTERVAL after one stalled async prewarm.
        self._wedged_until: dict = {}  # analysis: locked-by _wedge_lock
        self.n_latch_flips: dict = {}
        # verify_batch is called concurrently (async signature prewarm
        # worker + the SCP crank); the latch read/write (and the three
        # host_verify counters) go under one small lock so callers see
        # consistent state
        self._wedge_lock = threading.Lock()

    # A wedged device dispatch must never stall a caller indefinitely —
    # SCP envelope flushes run on the main crank and ledger close joins
    # the prewarm; the reference's inline libsodium path cannot hang, so
    # neither may this one.  After the budget the batch finishes on host
    # and the caller class LATCHES onto host for RETRY_INTERVAL (a
    # persistently-dead device costs at most one bounded stall per
    # interval, not one per batch).
    #
    # The budget follows the COMPILED SHAPE: the first dispatch of each
    # pow-2 bucket gets its program ready inside the call — it loads the
    # bucket's lowered program from the program store (ops/programs.py)
    # and the executable from the persistent cache, or, the first time on
    # a machine (and after an upgrade of JAX, libtpu or the kernel's
    # sources), traces, lowers, stores and compiles it — so a call gets
    # DEVICE_FIRST_TIMEOUT for every bucket it touches that has not run in
    # this process (BatchVerifier.cold_buckets), and DEVICE_TIMEOUT once
    # they all have.  A false latch on a healthy device would self-heal
    # after RETRY_INTERVAL, but silently moves the node's verifies onto
    # host meanwhile.  Measured on the one-chip v5e host (my chip runs,
    # PR 38; PERF.md "Where set-up goes"): a machine's first dispatch of a
    # bucket is 56-64 s (the Python trace 16-22 s, the export's lowering
    # 7 s, XLA + Mosaic 32-33 s, the serialise and the write 0.3-2.7 s); a
    # later process start loads the stored program and the executable in
    # 0.09-0.21 s a bucket (20-35 s until PR 38: it traced and lowered).
    # The default is sized for the machine's first start and leaves 2x
    # over it; it can shrink for every later one once a deployment's own
    # figures under the store are known (ROADMAP S9).
    # A node's own figures are in /info ``sig_backend`` ``first_dispatch``
    # (per bucket: trace_s, lower_s, compile_s, cache, caller, when), and
    # the flush that paid them is the ``sig.device_flush`` with ``cold``
    # on /trace.
    # The price: a device wedged at a bucket's FIRST dispatch holds its
    # caller that long before the host takes over.  Compiling the
    # reachable buckets at node start would let every live dispatch keep
    # DEVICE_TIMEOUT (open, ROADMAP S9).
    # Env-overridable: a loaded test host can push the interpret-mode
    # compile further (tests/conftest.py raises the compile budget for
    # exactly that; production keeps the defaults).  A malformed value
    # falls back to the default — a typo'd budget must not kill the node
    # at import.
    DEVICE_TIMEOUT = _env_float("STELLAR_TPU_DISPATCH_BUDGET", 15.0)
    DEVICE_FIRST_TIMEOUT = _env_float("STELLAR_TPU_FIRST_DISPATCH_BUDGET", 150.0)
    RETRY_INTERVAL = 60.0

    def _guarded(
        self, what: str, n: int, caller: str, cold: int, device_fn, host_fn
    ):
        """Run ``device_fn`` under the dispatch watchdog and the per-caller
        host latch; ``host_fn`` finishes the batch when the caller class
        is latched or the device outlasts its budget.  ``what`` is the
        surface ("verify" / "torsion"), ``cold`` the number of
        not-yet-compiled buckets the call touches."""
        span = f"sig.host_{what}"
        # the lock covers only the latch read/write — never the verify
        # work itself, or every concurrent caller inherits the slowest
        # batch's host-verify latency
        with self._wedge_lock:
            wedged = time.monotonic() < self._wedged_until.get(caller, 0.0)
        if wedged:
            self.n_wedge_fallback_items += n
            self._note_host_finish(what, caller, n)
            with self._tracer.span(
                span, items=n, reason="wedge-latch", caller=caller
            ):
                return host_fn()
        result: List[Any] = [None]
        err: List[BaseException] = []
        done = threading.Event()
        # what the worker (and the verifier's stager threads under it)
        # records names the span open on the caller's thread as its cause
        parent = self._tracer.current()

        def work():
            try:
                with self._tracer.under(parent):
                    result[0] = device_fn()
            except BaseException as e:
                err.append(e)
            finally:
                done.set()

        threading.Thread(
            target=work, name=f"tpu-{what}", daemon=True
        ).start()
        timeout = (
            self.DEVICE_FIRST_TIMEOUT * cold if cold else self.DEVICE_TIMEOUT
        )
        if not done.wait(timeout):
            with self._wedge_lock:
                # latch flips are metered per caller class so telemetry
                # (stats() → /info) shows WHICH plane is riding host
                self._wedged_until[caller] = (
                    time.monotonic() + self.RETRY_INTERVAL
                )
                self.n_latch_flips[caller] = (
                    self.n_latch_flips.get(caller, 0) + 1
                )
            self.n_wedge_fallback_items += n
            self._note_host_finish(what, caller, n)
            _log.warning(
                "device %s batch stalled >%.0fs (%d cold bucket(s));"
                " finishing %d items on host and latching the %r caller"
                " class onto host for %.0fs",
                what,
                timeout,
                cold,
                n,
                caller,
                self.RETRY_INTERVAL,
            )
            # the orphaned worker's eventual completion is harmless: the
            # caller-side cache scatter-back writes identical values
            with self._tracer.span(
                span, items=n, reason="device-stall", caller=caller
            ):
                return host_fn()
        if err:
            raise err[0]
        return result[0]

    def _note_caller(self, caller: str, where: str, n: int) -> None:
        if self.n_caller_items is None:
            self.n_caller_items = {}
        by = self.n_caller_items.setdefault(caller, {"device": 0, "host": 0})
        by[where] += n

    def _note_host_finish(self, what: str, caller: str, n: int) -> None:
        """A verify batch counted for the device (verify_batch) that the
        host finished after all: latched, or the device stalled."""
        if what == "verify":
            self._note_caller(caller, "device", -n)
            self._note_caller(caller, "host", n)

    def _host_verify(self, items: Sequence[VerifyTriple]) -> List[bool]:
        """libsodium over ``items``, its seconds counted: what every
        ``sig.host_verify`` span holds (the cutover here, the latch and the
        stall in ``_guarded``)."""
        t0 = time.perf_counter()
        oks = _sodium_verify_loop(items)
        dt = time.perf_counter() - t0
        # callers verify concurrently (the prewarm worker, the crank): the
        # three move together or a window's s / items is off
        with self._wedge_lock:
            self.host_verify_s += dt
            self.n_host_verify_calls += 1
            self.n_host_verify_items += len(items)
        return oks

    def verify_batch(
        self, items: Sequence[VerifyTriple], caller: str = CALLER_CLOSE
    ) -> List[bool]:
        if len(items) < self.cpu_cutover:
            self.n_cutover_items += len(items)
            self._note_caller(caller, "host", len(items))
            with self._tracer.span(
                "sig.host_verify", items=len(items), reason="cutover"
            ):
                return self._host_verify(items)
        self._note_caller(caller, "device", len(items))
        return self._device_flush(
            "verify",
            len(items),
            caller,
            True,
            lambda: self._verifier.verify(items),
            lambda: self._host_verify(items),
        )

    def _device_flush(
        self, what: str, n: int, caller: str, host_assist: bool,
        device_fn, host_fn,
    ):
        """The flush as its caller waits for it: the hop to the guarded
        worker, the stager pool, staging, dispatch and drain are the
        span's children.  ``req``: this backend's flush ordinal, where the
        flush is not already part of a ledger's close.  ``cold``, only
        when not 0: the buckets this flush dispatches for the first time
        in this process — the flush that ran under DEVICE_FIRST_TIMEOUT,
        with the close or the overlay flush that caused it as its parent.
        ``host_assist``: whether the verifier may peel a share off (not
        for torsion batches)."""
        cold = self._verifier.cold_buckets(n, host_assist=host_assist)

        def device():
            from ..ops import compile_events  # lazy: JAX import

            # this worker's thread lives for the one flush
            compile_events.serve(caller)
            return device_fn()

        self.n_device_flushes += 1
        with self._tracer.span(
            "sig.device_flush", req=self.n_device_flushes, items=n
        ) as sp:
            if sp is not None:
                sp.attrs["chunks"] = self._verifier.chunk_count(
                    n, host_assist=host_assist
                )
                if cold:
                    sp.attrs["cold"] = cold
            return self._guarded(what, n, caller, cold, device, host_fn)

    def torsion_check(
        self,
        encs: Sequence[bytes],
        caller: str = CALLER_OVERLAY,
        vals: Optional[Sequence] = None,
    ) -> List[bool]:
        """Prime-order proofs on the device batch plane: the verify
        kernel computes [L]·P == identity AS-IS via verify(A := P,
        h := L, s := 0, R := identity-encoding) — no hash stage at all
        (BatchVerifier.verify_torsion).  Same cutover arithmetic,
        watchdog and per-caller latch as verify_batch: small batches (and
        a latched/stalled device) ride the host ladder — with the
        caller's already-decoded ``vals`` when provided, so no second
        decompress pass — and the aggregate plane can never hang on a
        dead device."""

        def host():
            return SigBackend.torsion_check(
                self, encs, caller=caller, vals=vals
            )

        if len(encs) < self.cpu_cutover:
            self.n_cutover_torsion += len(encs)
            with self._tracer.span(
                "sig.host_torsion", items=len(encs), reason="cutover"
            ):
                return host()
        return self._device_flush(
            "torsion",
            len(encs),
            caller,
            False,
            lambda: self._verifier.verify_torsion(encs),
            host,
        )

    def stats(self) -> dict:
        s = self._verifier.stats()
        s["cpu_cutover_items"] = self.n_cutover_items
        s["cpu_cutover_torsion"] = self.n_cutover_torsion
        s["wedge_fallback_items"] = self.n_wedge_fallback_items
        s["wedge_latch_flips"] = dict(self.n_latch_flips)
        # host-assist items (a share the verifier peels off for libsodium
        # while the device works; off as shipped) are counted "device" here
        s["caller_items"] = {k: dict(v) for k, v in (self.n_caller_items or {}).items()}
        # s / items: what the host's verify costs a signature here
        s["host_verify"] = {
            "calls": self.n_host_verify_calls,
            "items": self.n_host_verify_items,
            "s": self.host_verify_s,
        }
        return s


def make_backend(
    kind: str = "cpu",
    cache: VerifySigCache = None,
    tracer=None,
    **kw,
) -> SigBackend:
    if kind == "cpu":
        inner: SigBackend = CpuSigBackend()
    elif kind == "tpu":
        inner = TpuSigBackend(tracer=tracer, **kw)
    else:
        raise ValueError(f"unknown SIGNATURE_BACKEND {kind!r}")
    if cache is None:
        from .keys import verify_cache

        cache = verify_cache()
    return CachingSigBackend(inner, cache, tracer=tracer)
