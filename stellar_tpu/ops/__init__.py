"""JAX/TPU kernels: GF(2^255-19) field arithmetic and batched ed25519 verify.

Importing this package enables JAX's persistent compilation cache, so a
bucket of the verify kernel compiles once per machine, not once per
process.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it
and nothing is set here; otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (the path is part of the cache key, so it must
not move between processes).

Beside the executables, in the subdirectory ``programs`` of the same
directory, ``ops/programs.py`` keeps each bucket's lowered verify program, so
that a process start loads it instead of tracing and lowering the kernel
again.  The subdirectory is made here, at import, and never later: what
counts the cache directory's own names never sees one appear, and JAX's
bookkeeping of its directory never meets a file it did not write.

It also registers, once a process, the listeners through which the program
hears what JAX traced, lowered and compiled (``compile_events``): JAX calls
them on the thread that compiles, so whoever is about to run a program for
the first time opens an account on its thread (``CompileEvents.charge``) and
reads afterwards what the stages cost.  They run only when JAX compiles:
the steady state pays nothing.
"""

import os
import threading
import time

import jax
from jax import monitoring

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

PROGRAMS_SUBDIR = "programs"
if jax.config.jax_compilation_cache_dir:
    try:
        os.makedirs(
            os.path.join(jax.config.jax_compilation_cache_dir, PROGRAMS_SUBDIR),
            exist_ok=True,
        )
    except OSError:
        # a cache directory this process may not write: the store is off
        # for it (ops/programs.py), as JAX's own cache is
        pass

# The three stages of a compilation as the installed JAX reports them
# (jax/_src/dispatch.py:60-62): wall intervals on the compiling thread, one
# after the other for a program, with those of what it calls inside them.
STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    # on a persistent-cache hit this is the read and the load of the
    # executable (compiler.py:435-452 runs inside it)
    "/jax/core/compile/backend_compile_duration": "compile_s",
}
# sums that lie inside ``compile_s`` (compiler.py:447-452), and the cache's
# two verdicts (compiler.py:446, compilation_cache.py:283: a miss is counted
# where the entry is written, so only for a compile of a second or more)
SUMS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "compile_time_saved_s",
}
COUNTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
# an interval that began within this of a later, longer one is inside it
_NEST_SLACK_S = 1e-3
_STAGE_FIELDS = frozenset(STAGES.values())


class StageTally:
    """Count and seconds of the stage events charged to it, and the bucket
    the last of them was charged with; shared between threads."""

    def __init__(self):
        self._lock = threading.Lock()
        self.events = 0  # analysis: locked-by _lock
        self.seconds = 0.0  # analysis: locked-by _lock
        self.last_bucket = None  # analysis: locked-by _lock

    def add(self, field: str, value, bucket) -> None:
        if field in _STAGE_FIELDS:
            with self._lock:
                if value > 0:
                    self.events += 1
                self.seconds += value
                self.last_bucket = bucket

    def stats(self) -> dict:
        with self._lock:
            return {
                "events": self.events,
                "seconds": self.seconds,
                "bucket": self.last_bucket,
            }


class CompileEvents:
    """The process's one pair of ``jax.monitoring`` listeners.

    An account is anything with ``add(field, value, bucket)``: ``field`` one
    of the names in ``STAGES`` / ``SUMS`` / ``COUNTS``, ``bucket`` what
    ``charge`` was given.  JAX reports the trace of every ``jit`` a program
    calls inside the program's own trace, and the small programs it runs
    while tracing inside that again; an interval that holds earlier ones
    takes their seconds back (``add`` with a negative value), so a second
    is counted once, under the outermost stage, and the stages of an
    account never sum to more than the wall time it was open."""

    def __init__(self):
        # the stage events no account was open for: the drain's small
        # programs, ``ops/sha512.py``, anything else
        self.unattributed = StageTally()
        # per thread, all set lazily (a backend's worker thread lives for
        # one flush): ``account``, where the thread's events go, None or
        # absent for ``unattributed``; ``bucket``, the verify bucket it is
        # dispatching; ``caller``, the caller class it works for;
        # ``intervals``, (start, end, field) of the stage intervals
        # counted so far for the account, none inside another
        self._on_thread = threading.local()
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def charge(self, account, bucket=None) -> None:
        """Open ``account`` on this thread (``None`` closes it)."""
        t = self._on_thread
        t.account = account
        t.bucket = bucket
        t.intervals = None  # nothing is inside an interval of another account

    def serve(self, caller) -> None:
        """Name the caller class (``crypto/sigbackend.py`` ``CALLER_*``)
        this thread's dispatches are for: the backend's worker on its own
        thread, the verifier on the stager threads it starts for it."""
        self._on_thread.caller = caller

    def serving(self):
        return getattr(self._on_thread, "caller", None)

    def _account(self, t):
        account = getattr(t, "account", None)
        return self.unattributed if account is None else account

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        t = self._on_thread
        bucket = getattr(t, "bucket", None)
        field = STAGES.get(event)
        if field is None:
            field = SUMS.get(event)
            if field is not None:
                self._account(t).add(field, seconds, bucket)
            return
        account = self._account(t)
        end = time.monotonic()
        start = end - seconds
        inside = getattr(t, "intervals", None)
        if inside is None:
            inside = t.intervals = []
        while inside and inside[-1][0] >= start - _NEST_SLACK_S:
            s, e, f = inside.pop()
            account.add(f, s - e, bucket)
        inside.append((start, end, field))
        account.add(field, seconds, bucket)

    def _on_event(self, event: str, **_kw) -> None:
        field = COUNTS.get(event)
        if field is not None:
            t = self._on_thread
            self._account(t).add(field, 1, getattr(t, "bucket", None))


# One a process, whatever imports this package and however often: a second
# import of the module (a reload, a second name) finds the first's on the
# module JAX keeps its listeners in, and registers nothing.
compile_events: CompileEvents = getattr(monitoring, "_stellar_tpu_compile_events", None)
if compile_events is None:
    compile_events = monitoring._stellar_tpu_compile_events = CompileEvents()
