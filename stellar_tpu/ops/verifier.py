"""The batch verifier: the host pipeline around the ed25519 verify kernel.

``BatchVerifier`` stages a batch (strict input gate + SHA-512(R‖A‖M) mod L
+ the packed upload layout, ``native/sighash.c`` or its numpy twin), pads
it to pow-2 buckets, uploads, dispatches and drains, and masks the device's
answers with the gate's.  The kernel's arithmetic is ``ops/ed25519.py``
(``ops/ed25519_pallas.py`` on a TPU); what a dispatch of a bucket calls,
and what the bucket's first dispatch cost, is ``ops/programs.py``.  Nothing
here is traced into a program, so an edit here keeps every stored one.
"""

from __future__ import annotations

import hashlib
import threading
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from . import compile_events
from . import ed25519 as ed
from . import ref25519 as ref
from .programs import BucketPrograms

PIPELINE_DEPTH = 2  # max in-flight device chunks in BatchVerifier.verify

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


class BatchVerifier:
    """Pads batches to pow-2 buckets (one XLA compile per bucket), runs the
    kernel, scatters results; host gate verdicts mask the device results,
    so a gate-rejected lane can never report True (and a chunk whose lanes
    ALL fail the gate skips its device round-trip entirely).

    ``backend="auto"`` picks the Pallas kernel (ops/ed25519_pallas.py —
    measured 4× the XLA lowering on v5e in round 3) on a real
    accelerator and the plain XLA kernel on CPU.  With a mesh, the Pallas
    kernel runs PER SHARD under shard_map (each chip grids its local
    slice of the batch), so multi-chip keeps the fast kernel.  The
    compiled program holds no collective at all: the verdicts stay
    sharded (``out_shardings``) and the drain reads each chip's piece
    (the 2x2 v5e host's trace, PR 45: four planes, the same two kernel
    programs on each, nothing else)."""

    def __init__(
        self,
        max_batch: int = 4096,
        mesh=None,
        min_device_batch: int = 16,
        backend: str = "auto",
        streams: int = 1,
        host_assist: float = 0.0,
        native_hash: bool = True,
        device_hash: bool = False,
        tracer=None,
        shared_programs: bool = False,
    ):
        from ..trace import NULL_TRACER

        self._tracer = tracer if tracer is not None else NULL_TRACER
        self.max_batch = max_batch
        self.min_device_batch = min_device_batch
        self.mesh = mesh
        # Device-resident hash stage (ops/sha512.py; Config.DEVICE_HASH):
        # the single-block SHA-512(R‖A‖M) mod L runs fused ahead of the
        # verify kernel in the same jit, staging uploads RAW bytes (160
        # rows/item) and the host keeps only the strict gate; multi-block
        # (>111-byte preimage) residuals ride the C hash path and merge via
        # the flag row.  Off (default, like SIG_MESH) = the host-hash
        # 128-row path, bit-exact either way.
        self.device_hash = bool(device_hash)
        if self.device_hash:
            from . import sha512 as _dsha

            self._rows = _dsha.DH_ROWS
        else:
            self._rows = 128
        # Host stage: the native C extension (gate + batch SHA-512 mod L +
        # packed staging with the GIL released — native/sighash.c) when it
        # builds, else the hashlib/numpy fallback, which native_hash=False
        # pins (the differential tests' other side).
        self._sighash = None
        if native_hash:
            from .. import native as _native

            self._sighash = _native.load_sighash()
        self._pool = _StagingPool()
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
        # transfer pipelines with the kernel
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
            from . import ed25519_pallas as pallas

            # every device batch must be a whole number of pallas tiles —
            # PER SHARD when a mesh splits the batch axis
            self._granule = pallas.NT * n_shards
            lowering = {
                "NT": pallas.NT,
                "batch_inv": pallas._BATCH_INV,
                "signed_win": pallas._SIGNED_WIN,
            }
        else:
            # every bucket must split evenly over the mesh's batch axis:
            # staging is one fixed-width buffer per shard, and a chunk
            # whose length is not divisible by n_shards pads the tail
            # shard (masked at drain — see _stage_chunk_sharded)
            self._granule = n_shards
            # the lane-tree batched inversion is safe only where the batch
            # axis is unsharded (ed.compress)
            lowering = {"batch_inv": mesh is None}
        if self._granule > 1:
            self.max_batch = max(
                self._granule,
                -(-self.max_batch // self._granule) * self._granule,
            )
        # (input, output) shardings under a mesh: _upload_sharded assembles
        # each chunk's per-shard staging buffers under exactly the first, so
        # the jit never inserts a reshard in front of the kernel
        self._shardings = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as PSpec

            batch_axis = mesh.axis_names[0]
            self._shardings = (
                NamedSharding(mesh, PSpec(None, batch_axis)),
                NamedSharding(mesh, PSpec(batch_axis)),
            )
        # what a dispatch of each bucket calls, and what each bucket's first
        # dispatch cost: stats()["first_dispatch"]
        self._programs = BucketPrograms(
            self._make_kernel(lowering["batch_inv"]),
            rows=self._rows,
            backend=self.backend,
            interpret=self.interpret,
            device_hash=self.device_hash,
            lowering=lowering,
            mesh=mesh,
            shardings=self._shardings,
            shared=shared_programs,
        )
        self.n_device_calls = 0
        self.n_lanes = 0
        self.n_items = 0
        self.n_gate_rejects = 0
        self.n_host_assist_items = 0
        self.n_torsion_items = 0
        # under a mesh, of the chunks dispatched: shard buffers that held no
        # live lane, and the live lanes each device was handed, in the
        # mesh's order: stats()["mesh"]
        self.n_dead_shards = 0
        self._device_lanes = [0] * n_shards if mesh is not None else []
        # the counters above are bumped from every stager thread; += alone
        # drops increments under streams>1 and they feed profiling
        # conclusions
        self._calls_lock = threading.Lock()

    def _make_kernel(self, batch_inv: bool):
        """-> the jit over the packed (128, N) — or, with device_hash,
        (160, N) — uint8 staging array.

        ONE host->device upload carries the whole chunk (A/R/s/h byte
        rows, or A/R/s/raw-M under device_hash); the row slicing, int32
        widening, nibble splitting — and with device_hash the whole
        SHA-512 mod L stage (ops/sha512.py) — all happen inside the jit
        program, so the host never touches the hash path for the
        dominant single-block class.  ``batch_inv`` is the XLA body's
        (the Pallas kernel reads its module's constant)."""
        if self.backend == "pallas":
            from .ed25519_pallas import verify_kernel_pallas

            interpret, device_hash = self.interpret, self.device_hash

            def packed_pallas(p):
                if device_hash:
                    from .sha512 import sha512_pallas

                    # the sha stage grids the same batch tiles (per shard
                    # under a mesh), so both pallas_calls fuse into one jit
                    # with no cross-shard communication
                    h = sha512_pallas(p, interpret=interpret).astype(jnp.uint8)
                else:
                    h = p[96:128]
                return verify_kernel_pallas(
                    p[0:32], p[32:64], p[64:96], h, interpret=interpret
                )

            body = packed_pallas
        else:
            body = partial(
                ed._verify_packed_device_hash
                if self.device_hash
                else ed._verify_packed,
                batch_inv=batch_inv,
            )
        if self.mesh is None:
            return jax.jit(body)
        shard, vec = self._shardings
        if self.backend == "pallas":
            from jax import shard_map

            # PER SHARD: each chip grids its local slice of the batch
            body = shard_map(
                body,
                mesh=self.mesh,
                in_specs=(shard.spec,),
                out_specs=vec.spec,
                # pallas_call's out_shape carries no varying-mesh-axes
                # annotation; the per-shard kernel is trivially
                # batch-varying, so skip the VMA check
                check_vma=False,
            )
        return jax.jit(body, in_shardings=(shard,), out_shardings=vec)

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
        return self._programs.cold(sizes)

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
        try:
            self._run_pipeline(
                items, self._chunks(n_dev), out, self._stage_chunk, "ed25519.drain"
            )
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
        self._run_pipeline(
            encs,
            self._chunks(len(encs)),
            out,
            self._stage_torsion,
            "ed25519.torsion_drain",
        )
        return out

    def _drain(self, out, span: str, rng, staged: Optional[_Staged], fut):
        """Read one chunk's answers into ``out`` and give its buffers back."""
        start, n = rng
        dsp = self._tracer.begin(span)
        if fut is not None:
            out[start : start + n] = self._read_back(fut, staged, n)
        # fut None: every lane was gate-rejected — out[] rows stay False
        # without a device round-trip
        self._tracer.end(dsp, items=n)
        if staged is not None:
            self._pool.release(staged.bufs)

    def _read_back(self, fut, staged: _Staged, n: int) -> List[bool]:
        """The two halves of a drain, as children that partition its span:
        the wait until the device's answer is ready, then the rest of the
        device -> host copy, the gate mask and the list.  The wait first
        queues the copy behind the kernel, as ``np.asarray`` on a pending
        result does: waiting and only then copying costs a host round trip
        a chunk (~120 us, my chip run, PR 24).  Under a mesh ``fut`` is
        sharded: one copy a chip, joined on the host by ``np.asarray``."""
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
        return self._stage(self._fill_torsion, encs, start, n)[0]

    @staticmethod
    def _fill_torsion(encs, start, n, packed, okbuf) -> int:
        """numpy fill of one torsion chunk; -> lanes gated out.  The device decompress does
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
        return n - int(ok.sum())

    def _run_pipeline(self, items, chunks, out, stage, drain_span: str):
        """Stage, dispatch and drain ``chunks`` of ``items`` into ``out``.

        Pipelined with bounded depth: a stager thread stages AND
        dispatches chunk k+1 (the C host stage releases the GIL for the
        whole gate+hash+staging pass) while the main thread blocks
        draining chunk k-1 from the device; at most PIPELINE_DEPTH
        chunks of device buffers are ever in flight (unbounded dispatch
        could OOM the chip on huge replays)."""
        if len(chunks) <= 1:
            for rng in chunks:
                staged = stage(items, *rng)
                fut = self._dispatch_staged(staged)
                self._drain(out, drain_span, rng, staged, fut)
            return
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
                self._drain(out, drain_span, rng, *f.result())

            try:
                for rng in chunks:
                    if len(futs) - drained >= depth:
                        drain_oldest()
                    futs.append((rng, stager.submit(stage_and_dispatch, rng)))
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
        upload layout, into pooled staging.  The native C stage
        releases the GIL for the whole pass (and fans out over its
        internal thread pool on large chunks), so a stager thread running
        this genuinely overlaps device compute; the hashlib/numpy
        fallback covers toolchain-less hosts."""
        if n == 0:
            return None
        sp = self._tracer.begin("ed25519.host_hash")
        staged, rejects = self._stage(self._stage_into, items, start, n)
        shards = (
            {} if self.mesh is None else {"shards": len(self.mesh.devices.flat)}
        )
        self._tracer.end(
            sp,
            items=n,
            native=self._sighash is not None,
            rejects=rejects,
            device_hash=self.device_hash,
            **shards,
        )
        if rejects:
            with self._calls_lock:  # stager threads update concurrently
                self.n_gate_rejects += int(rejects)
        return staged

    def _stage(self, fill, items, start, n) -> Tuple[_Staged, int]:
        """``items[start:start+n]`` into pooled staging through
        ``fill(items, start, count, packed, okbuf) -> rejects``; returns
        the chunk and its rejects.

        Unsharded: one ``(rows, bucket)`` buffer.  Mesh: one pooled
        ``(rows, bucket // n_shards)`` buffer PER SHARD, each filled by its
        own pass (the native C stage releases the GIL per call; under
        device_hash it is gate + raw-byte packing only, so no chip pays a
        full C hash pass) and uploaded straight to its chip in
        _dispatch_staged — the global chunk is never repacked on host.
        Live lanes occupy global columns [0, n) shard-major; a chunk not
        divisible by n_shards pads the tail shard and shards past the live
        range stage nothing (zeroed, inert lanes), so the drain's [:n] mask
        makes remainders bit-exact with the unsharded path."""
        bucket = self._bucket(n)
        if self.mesh is None:
            bufs = self._pool.acquire(bucket, self._rows)
            packed, okbuf = bufs
            rejects = fill(items, start, n, packed, okbuf)
            return _Staged(packed, okbuf[:n].astype(bool), n, bufs), rejects
        shard_bucket = bucket // len(self.mesh.devices.flat)
        bufs = []
        ok = np.empty(n, dtype=bool)
        rejects = 0
        for lo in range(0, bucket, shard_bucket):
            pair = self._pool.acquire(shard_bucket, self._rows)
            bufs.append(pair)
            packed, okbuf = pair
            cnt = min(shard_bucket, max(0, n - lo))
            if cnt == 0:
                packed[:] = 0  # dead shard: every lane is inert padding
                continue
            rejects += fill(items, start + lo, cnt, packed, okbuf)
            ok[lo : lo + cnt] = okbuf[:cnt].astype(bool)
        return _Staged([p for p, _ in bufs], ok, n, tuple(bufs)), rejects

    def _stage_into(self, items, start, n, packed, okbuf) -> int:
        """One host-stage pass into a pooled buffer: the C extension when
        it built (GIL released for the whole pass), else the Python
        fallback — routed by layout.  Host-hash: gate + SHA-512 mod L +
        (128, ·) staging.  Device-hash: gate + raw-byte (160, ·) staging."""
        if self._sighash is None:
            return self._stage_py(items, start, n, packed, okbuf)
        stage = self._sighash.stage_raw if self.device_hash else self._sighash.stage
        # threads left at the C stage's auto: its pool for large chunks
        return stage(items, start, n, packed, okbuf, _BLACKLIST)

    def _stage_py(self, items, start, n, packed, okbuf) -> int:
        """Pure-Python host stage (hashlib + the vectorized numpy gate)
        filling the layout this verifier uploads — the no-toolchain twin
        of native ``stage`` / ``stage_raw`` and the differential tests'
        other side.  Under device_hash a single-block message goes up raw
        (flag 1) and only the multi-block residual class is hashed here."""
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
                if self.device_hash and len(msg) <= dsha.MAX_DEVICE_MSG:
                    if msg:
                        packed[96 : 96 + len(msg), j] = np.frombuffer(
                            msg, dtype=np.uint8
                        )
                    packed[dsha.ROW_MLEN, j] = len(msg)
                    packed[dsha.ROW_FLAG, j] = 1
                    continue
                h = (
                    int.from_bytes(
                        sha(sig[:32] + pk + msg).digest(), "little"
                    )
                    % ref.L
                )
                packed[96:128, j] = np.frombuffer(
                    h.to_bytes(32, "little"), dtype=np.uint8
                )
        packed[:, n:] = 0
        okbuf[:n] = ok
        return n - int(ok.sum())

    def _dispatch_staged(self, staged: Optional[_Staged]):
        """Upload the packed staging buffer (ONE transfer; one a shard under
        a mesh: the ``ed25519.upload`` span) and launch the kernel.  Runs
        on the stager thread in the multi-chunk pipeline, on the caller's
        thread for single-chunk batches.  Returns the
        in-flight device result, or None when every lane was
        gate-rejected (hostile floods never reach the chip).

        What the bucket's dispatch calls is ``ops/programs.py``'s to say;
        a bucket's first dispatch in this process is accounted for there,
        and this one span carries the record."""
        if staged is None or not staged.ok.any():
            return None
        dsp = self._tracer.begin("ed25519.device_dispatch")
        shards = staged.packed if self.mesh is not None else [staged.packed]
        shard_bucket = shards[0].shape[1]
        bucket = shard_bucket * len(shards)
        with self._programs.dispatch(bucket) as (call, first):
            # the host->device copy, apart from the program's call
            with self._tracer.span("ed25519.upload"):
                if self.mesh is not None:
                    arr = self._upload_sharded(shards)
                else:
                    arr = jnp.asarray(staged.packed)
            # returns once the program is compiled and the execution enqueued
            ok = call(arr)
        self._tracer.end(
            dsp,
            bucket=bucket,
            backend=self.backend,
            shards=len(shards),
            upload_bytes=self._rows * bucket,
            **first,
        )
        with self._calls_lock:
            self.n_device_calls += 1
            self.n_lanes += bucket
            if self.mesh is not None:
                for i in range(len(shards)):
                    live = min(shard_bucket, max(0, staged.n - i * shard_bucket))
                    self._device_lanes[i] += live
                    self.n_dead_shards += int(live == 0)
        return ok

    def _upload_sharded(self, shards):
        """One host->device transfer PER SHARD: each chip's C-contiguous
        staging buffer goes straight to that chip, and the global chunk
        array is assembled from the single-device pieces under the exact
        sharding the jitted kernel expects — XLA inserts no reshard, and
        the round-trip has no collective: the (N,) verdicts come back
        sharded and ``_read_back`` copies one piece a chip to the host."""
        devices = list(self.mesh.devices.flat)
        singles = [
            jax.device_put(buf, dev) for buf, dev in zip(shards, devices)
        ]
        bucket = sum(buf.shape[1] for buf in shards)
        return jax.make_array_from_single_device_arrays(
            (self._rows, bucket), self._shardings[0], singles
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
            "first_dispatch": self._programs.stats(),
            # 0 = unsharded single-queue dispatch; >0 = chips on the
            # batch-axis mesh (Config.SIG_MESH; bench close lines carry
            # this as sig_mesh_devices so every JSON records the mode)
            "mesh_devices": len(self._device_lanes),
            # what the mesh's chips were handed (all 0 / empty unsharded):
            # host->device copies, one a shard a chunk; shard buffers with
            # no live lane (a short tail chunk's: inert padding the chip
            # still computes); live lanes by device, in the mesh's order
            "mesh": {
                "devices": len(self._device_lanes),
                "shard_uploads": self.n_device_calls * len(self._device_lanes),
                "dead_shards": self.n_dead_shards,
                "lanes_per_device": list(self._device_lanes),
            },
        }
