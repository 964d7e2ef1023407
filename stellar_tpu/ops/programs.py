"""A callable for each bucket of the verify kernel, and the record of how it
was made: the program store, and a verifier's books over it.

**The store** keeps a bucket's lowered verify program beside the executable
JAX's persistent compilation cache keeps of it.  The cache spares a process
the compile; it does not spare it running the kernel's Python body to a
jaxpr and walking that jaxpr to MLIR, which is 98-99 % of a bucket's first
dispatch on a cache hit (PERF.md "Where set-up goes").  The lowered program
is the same bytes whoever lowers it, so the first process to lower a bucket
serialises it (``jax.export``) into ``<cache dir>/programs/<key>`` and every
later one loads it from there.

The key is a SHA-256 over everything that decides the lowered program
(``BucketPrograms.fields``: the bytes of the kernel's sources, the versions
of JAX, jaxlib and the backend, the device, the mesh, the bucket, the
layout, the lowering and its flags) and nothing else — no path, host name or
call stack, so two checkouts of one tree that share a cache directory share
their programs, and a one-chunk flush and a two-chunk flush ask for one file
(and, through it, one executable).  The sources are ``SOURCE_FILES``, the
five files the kernel's body is traced through.  The verifier's host code
(``ops/verifier.py``: staging, dispatch, drain) is in none of them and this
module is not either, so an edit to the pipeline or to these books keeps
every stored program.

A file is as trusted as the executables JAX loads from the same directory;
deleting the directory, or any file in it, is safe: the next process lowers
the bucket again.  A file that does not read back whole is never run: it
carries the digest of its payload in front.

**The books** (``BucketPrograms``, one a verifier): the verifier hands over,
once, the kernel as ``jax.jit`` traces it and the facts that decide a
program; a dispatch asks for its bucket's callable (``dispatch``), and the
bucket's first dispatch in the process — the load, or the trace, lowering
and store, and the compile — is accounted for from what JAX reports on the
dispatching thread (``ops/__init__.py`` ``CompileEvents``).  Nothing here
imports the verifier: the arrows run verifier -> programs -> the kernel it
was handed.
"""

from __future__ import annotations

import contextlib
import functools
import hashlib
import json
import os
import threading
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import export

from ..util import fs, xlog
from . import PROGRAMS_SUBDIR, STAGES, SUMS, StageTally, compile_events

_log = xlog.logger("Tx")

# Every source file the verify kernel's body is traced through, XLA and
# Pallas lowering alike (their imports: ed25519 -> fe, ref25519, sha512;
# ed25519_pallas -> fe, ed25519; sha512 -> fe, ref25519).  A module the body
# can reach and this list leaves out is a stale program waiting to happen.
SOURCE_FILES = (
    "ed25519.py",
    "ed25519_pallas.py",
    "fe.py",
    "sha512.py",
    "ref25519.py",
)
_OPS_DIR = os.path.dirname(os.path.abspath(__file__))
_DIGEST_BYTES = hashlib.sha256().digest_size


class BadProgramFile(ValueError):
    """A stored program whose payload is not the one its digest names."""


def store_dir() -> Optional[str]:
    """Where the programs live, or None where there is nowhere to keep
    them: no cache directory, or one whose ``programs`` could not be made
    at import (``ops/__init__.py``)."""
    cache = jax.config.jax_compilation_cache_dir
    if not cache:
        return None
    d = os.path.join(cache, PROGRAMS_SUBDIR)
    return d if os.path.isdir(d) else None


@functools.lru_cache(maxsize=None)
def source_digests(root: str = _OPS_DIR) -> Tuple[Tuple[str, str], ...]:
    """(file, SHA-256 of its bytes) for each of ``SOURCE_FILES``; read once
    a process: the modules are imported by then, and what a later edit of
    the file says is not what this process would trace."""
    out = []
    for name in SOURCE_FILES:
        with open(os.path.join(root, name), "rb") as f:
            out.append((name, hashlib.sha256(f.read()).hexdigest()))
    return tuple(out)


def key(fields: dict) -> str:
    """The store's name for the program ``fields`` decide."""
    canon = json.dumps(fields, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(canon.encode()).hexdigest()


def path_of(directory: str, fields: dict) -> str:
    return os.path.join(directory, key(fields) + ".jaxexport")


def load(path: str) -> Optional[export.Exported]:
    """The program stored at ``path``; None where there is none.  Raises
    where there is a file and it is not a whole program."""
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except FileNotFoundError:
        return None
    digest, payload = blob[:_DIGEST_BYTES], blob[_DIGEST_BYTES:]
    if not payload or hashlib.sha256(payload).digest() != digest:
        raise BadProgramFile(path)
    return export.deserialize(bytearray(payload))


def save(path: str, exported: export.Exported) -> export.Exported:
    """Store ``exported`` at ``path`` (tmp -> fsync -> rename: two writers
    of one key leave one whole file) and return it as a later process will
    read it, so that the first process runs, and caches the executable of,
    the very program the others load."""
    payload = bytes(exported.serialize())
    fs.durable_write(path, hashlib.sha256(payload).digest() + payload)
    return export.deserialize(bytearray(payload))


def discard(path: str) -> None:
    """Remove a file that could not be used; the next process stores the
    bucket's program again."""
    try:
        os.unlink(path)
    except OSError:
        pass


# ---------------------------------------------------------------------------
# the account of a bucket's first dispatch
# ---------------------------------------------------------------------------

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
    (``BucketPrograms._first_program``): ``"stored"`` — loaded from the
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


# ---------------------------------------------------------------------------
# a verifier's programs
# ---------------------------------------------------------------------------

# What the nodes of one process share (``BucketPrograms(shared=True)``, as
# every Application's backend asks): by everything that decides the traced
# kernel, the kernel, what a dispatch of each bucket calls, and the record
# of each bucket's first dispatch in this process.  A second node — a
# catch-up's fresh one, a simulation's — then loads, traces and compiles
# nothing for a bucket the process has run.
_process_programs: dict = {}
_process_programs_lock = threading.Lock()


class BucketPrograms:
    """What one verifier's dispatches call, a bucket each, and what each
    bucket's first dispatch cost.

    ``kernel`` is the kernel as ``jax.jit`` traces it, a trace a shape:
    what a bucket's program is exported from, and what runs a bucket for
    which the program store cannot be used.  The rest are the facts that,
    with the bucket and the process's JAX and device, decide the lowered
    program (``fields``): the staging layout's ``rows``, the ``backend``
    and whether it is interpreted, ``device_hash``, the ``mesh`` with the
    ``(input, output)`` ``shardings`` the verifier uploads and reads under,
    and ``lowering`` — the constants the traced body branches on
    (``batch_inv``; for the Pallas lowering its ``NT`` and ``signed_win``
    too), by value."""

    def __init__(
        self,
        kernel,
        *,
        rows: int,
        backend: str,
        interpret: bool,
        device_hash: bool,
        lowering: dict,
        mesh=None,
        shardings=None,
        shared: bool = False,
    ):
        self.kernel = kernel
        self.rows = rows
        self.backend = backend
        self.interpret = interpret
        self.device_hash = device_hash
        self.lowering = lowering
        self.mesh = mesh
        self.shardings = shardings
        # what a dispatch of a bucket calls, made once at the bucket's
        # first dispatch (_first_program) and kept: the jit of its stored
        # program, or self.kernel.  Never a new jit a dispatch: that would
        # trace the wrapper again at every flush
        self._calls: dict = {}  # analysis: locked-by _lock
        # shared: kernel and calls are the process's (above), and a bucket
        # another verifier of this process dispatched first is warm here
        # too, under that dispatch's record
        self._process_firsts: Optional[dict] = None
        if shared:
            which = (backend, interpret, device_hash, mesh)
            with _process_programs_lock:
                self.kernel, self._calls, self._process_firsts = (
                    _process_programs.setdefault(which, (kernel, {}, {}))
                )
        # buckets whose program has been loaded or lowered, and compiled,
        # in this process (one executable per padded batch size; layout,
        # mesh and lowering are fixed per verifier, and torsion proofs ride
        # the same program) — what cold() sizes a caller's watchdog budget
        # from
        self._warm_buckets: set = set()  # analysis: locked-by _lock
        # what each bucket's first dispatch cost, by bucket, and the stage
        # events of dispatches after it: stats()
        self._first_dispatches: dict = {}  # analysis: locked-by _lock
        self._recompiles = StageTally()
        self._lock = threading.Lock()

    def fields(self, bucket: int) -> dict:
        """Everything that decides the program a bucket lowers to, and
        nothing that does not: a stale program is a wrong verdict, so where
        in doubt a field is in."""
        import jaxlib

        dev = jax.devices()[0]
        return {
            "sources": source_digests(),
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
            "rows": self.rows,
            "backend": self.backend,
            "interpret": self.interpret,
            "device_hash": self.device_hash,
            **self.lowering,
        }

    def cold(self, buckets: set) -> int:
        """How many of ``buckets`` have not run in this process yet."""
        with self._lock:
            return len(
                buckets - self._warm_buckets - set(self._process_firsts or ())
            )

    @contextlib.contextmanager
    def dispatch(self, bucket: int):
        """-> ``(call, first)`` for one dispatch of ``bucket``: upload and
        call inside the block.

        A bucket's first dispatch in this process loads its lowered program
        from the program store — or traces and lowers it, and stores it —
        and compiles it (``_first_program``): the thread opens an account
        for what JAX reports of that until the block ends, and the record
        goes to ``stats()``, into one log line and, once the block has
        ended, into ``first``: what of it the dispatch's span carries
        (empty for a bucket that has run, and for the loser of two threads
        that dispatched one cold bucket at once).  A later dispatch marks
        its thread too, so that a compilation that should not happen any
        more is counted against its bucket."""
        with self._lock:
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
        first: dict = {}
        compile_events.charge(account, bucket)
        try:
            if cold:
                call = self._first_program(bucket, account)
                if call is not self.kernel:
                    call = functools.partial(
                        self._first_call, bucket, account, call
                    )
            yield call, first
        finally:
            compile_events.charge(None)
        if cold:
            first.update(self._note_first_dispatch(account.close()))

    def _first_program(self, bucket: int, account: _FirstDispatch):
        """-> what this verifier calls for ``bucket`` from now on.  A hit
        of the program store deserialises the bucket's program; a miss
        exports it from ``self.kernel`` — the one trace and lowering this
        machine pays for the bucket — stores it, and runs through the
        stored program on this process too, so that the executable the
        persistent cache keeps is the one every later process asks for.
        Whatever goes wrong leaves the bucket on ``self.kernel``, is
        logged once and counted (``programs_traced``); it never fails a
        flush, and nothing is tried again for the bucket in this process."""
        t0 = time.monotonic()
        try:
            directory = store_dir()
            if directory is None:
                raise FileNotFoundError("no directory for the program store")
            path = account.program_path = path_of(
                directory, self.fields(bucket)
            )
            try:
                exported = load(path)
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
                traced = export.export(self.kernel)(
                    jax.ShapeDtypeStruct((self.rows, bucket), jnp.uint8)
                )
                if account.trace_lower_s() <= seen:
                    # a JAX that reports no stage from inside the export:
                    # the call's own time, or the account would go blind
                    # on the one path that still costs a minute
                    account.add("trace_s", time.monotonic() - t1, bucket)
                exported = save(path, traced)
                account.program = PROGRAM_EXPORTED
            if self.shardings is not None:
                shard, vec = self.shardings
                call = jax.jit(
                    exported.call, in_shardings=(shard,), out_shardings=vec
                )
            else:
                call = jax.jit(exported.call)
        except Exception as e:
            call = self._program_unusable(bucket, account, e)
        with self._lock:
            # of two threads at one cold bucket both run what the first kept
            return self._calls.setdefault(bucket, call)

    def _program_unusable(self, bucket, account, err):
        """The program store failed ``bucket``: remove the file where there
        is one, say so once, and leave the bucket on the traced kernel."""
        if account.program_path is not None:
            discard(account.program_path)
        account.program = PROGRAM_TRACED
        account.program_error = type(err).__name__
        _log.warning(
            "bucket %d: no stored program (%s: %s); tracing the kernel",
            bucket,
            type(err).__name__,
            err,
        )
        return self.kernel

    def _first_call(self, bucket, account, call, arr):
        """A stored program's first call, where it is lowered into its
        wrapper and compiled: a module that does not parse or a program
        that refuses the platform or the device count shows here."""
        try:
            return call(arr)
        except Exception as e:
            with self._lock:
                self._calls[bucket] = self._program_unusable(bucket, account, e)
            return self.kernel(arr)

    def _note_first_dispatch(self, rec: dict) -> dict:
        """Keep and log the record of a bucket's first dispatch; returns
        what of it the dispatch's span carries (nothing for the loser of
        two threads that dispatched one cold bucket at once)."""
        bucket = rec["bucket"]
        with self._lock:
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

    def stats(self) -> dict:
        """Where the seconds of each bucket's first dispatch went, as JAX
        reported them on the dispatching thread (counted whether or not
        the tracer is on; monotonic).  ``wall_s`` is the length of the
        union of the records' intervals: two buckets first dispatched on
        two threads interleave under the interpreter lock, and their sum
        would count the overlap twice.  ``unattributed``: stage events of
        the whole process that no dispatch was open for; ``recompiles``:
        those of a dispatch whose bucket had run before — 0 on a healthy
        node, whatever its age."""
        with self._lock:
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
