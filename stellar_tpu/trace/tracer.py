"""Span tracer — structured phase profiling for the node's hot paths.

The reference attributes latency with libmedida timers embedded throughout
(SURVEY/PAPER.md layer 0); ``util/metrics.py`` reproduces the counting side
but cannot say *where inside a ledger close* the time went.  This module adds
the missing attribution plane:

- ``Tracer.span(name, **attrs)`` — context manager for synchronous phases;
  ``begin``/``end`` for phases that start and finish on different callbacks
  or threads (async prewarm joins, item fetches, SCP rounds).
- every span names its cause: ``sid`` (an integer of this tracer),
  ``parent`` (the ``sid`` of the span that was open on the same thread when
  it began) and ``req`` (the request it belongs to: the ledger sequence
  under ``ledger.close`` / ``herder.trigger``, a flush ordinal under a
  signature flush).  Each thread keeps a stack of its open spans;
  ``span()`` and ``begin``/``end`` push and pop it, and ``end`` unwinds
  whatever was begun above the span and never ended (an exception between
  a ``begin`` and its ``end``).  Work handed to another thread names its
  parent itself: ``parent=sp`` (``span`` or ``begin``) for one span,
  ``under(sp)`` around everything a worker records for the caller; ``current()`` is what
  the caller hands over.  A span that outlives the scope that began it, or
  that another thread or a later callback ends (``overlay.fetch``,
  ``scp.*``), is begun ``detached=True``: it has a parent and is nobody's.
  ``req`` is the parent's where the parent has one, else the ``req=`` the
  span was begun with.  ``selftime.py`` computes self time from parents.
  A span at the bottom of a long-lived thread's stack is a ``span()``
  block, ended on every path: a ``begin`` that an exception skipped would
  stay there, a stale parent of whatever the thread records next.
  (``begin``/``end`` pairs run under such a block, under ``ledger.close``,
  which ends on its failure path, or on a worker's thread under ``under``.)
- a lock-protected fixed-size ring buffer of completed spans (old spans are
  overwritten, the tracer never grows without bound);
- per-name latency aggregation: every completed span feeds a reservoir
  ``Histogram`` registered in the app's ``MetricsRegistry`` under
  ``trace.<name>``, so ``/metrics`` carries count/p50/p95/max for free;
- Chrome ``trace_event`` export (``chrome.py``) for ``/trace``.

Timestamps come from the owning ``Application``'s VirtualClock when that
clock runs in VIRTUAL mode — spans recorded under simulation tests are
bit-for-bit deterministic.  Real-time clocks (and no clock at all) fall back
to ``time.monotonic`` so wall-clock jumps can never produce negative
durations.

A disabled tracer (``Config.TRACE_ENABLED = false``) short-circuits to a
shared no-op scope before touching the ring or the clock; ``NULL_TRACER`` is
the module-wide disabled instance components use when no Application wired a
real one in (keeps every call site unconditional).
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, List, Optional

from ..util.metrics import Histogram


class Span:
    """One completed (or in-flight) phase.  ``start``/``end`` are seconds on
    the tracer's clock; ``attrs`` land in the Chrome export's ``args``, with
    ``sid``, ``parent`` (a ``sid`` or None) and ``req`` (or None)."""

    __slots__ = ("name", "start", "end", "tid", "attrs", "sid", "parent", "req")

    def __init__(
        self,
        name: str,
        start: float,
        tid: int,
        attrs: Optional[dict],
        sid: int = 0,
        parent: Optional[int] = None,
        req=None,
    ):
        self.name = name
        self.start = start
        self.end: Optional[float] = None
        self.tid = tid
        self.attrs = attrs
        self.sid = sid
        self.parent = parent
        self.req = req

    @property
    def duration(self) -> float:
        return (self.end - self.start) if self.end is not None else 0.0

    def __repr__(self) -> str:  # debugging aid only
        return (
            f"Span({self.name!r}, {self.start:.6f}..{self.end}, {self.attrs},"
            f" sid={self.sid}, parent={self.parent}, req={self.req})"
        )


class _NoopScope:
    """Shared do-nothing context manager returned by a disabled tracer."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP_SCOPE = _NoopScope()


class _SpanScope:
    __slots__ = ("_tracer", "_span")

    def __init__(self, tracer: "Tracer", span: Span):
        self._tracer = tracer
        self._span = span

    def __enter__(self) -> Span:
        return self._span

    def __exit__(self, *exc):
        self._tracer.end(self._span)
        return False


class _UnderScope:
    """``Tracer.under``: a span of another thread at the bottom of this
    thread's stack for the length of the block."""

    __slots__ = ("_stack", "_parent")

    def __init__(self, stack: list, parent: Span):
        self._stack = stack
        self._parent = parent

    def __enter__(self) -> Span:
        self._stack.append(self._parent)
        return self._parent

    def __exit__(self, *exc):
        _unwind(self._stack, self._parent)
        return False


def _unwind(stack: list, span: Span) -> None:
    """Take ``span`` off ``stack`` with everything begun above it."""
    if stack:
        if stack[-1] is span:
            stack.pop()
            return
        for i in range(len(stack) - 2, -1, -1):
            if stack[i] is span:
                del stack[i:]
                return


class Tracer:
    """Per-Application span recorder (see module docstring)."""

    def __init__(
        self,
        enabled: bool = True,
        ring_size: int = 8192,
        clock=None,
        metrics=None,
    ):
        self.enabled = bool(enabled)
        if ring_size < 1:
            raise ValueError("ring_size must be >= 1")
        self.ring_size = int(ring_size)
        self._ring: List[Optional[Span]] = [None] * self.ring_size
        self._idx = 0  # total completed spans ever (ring cursor = idx % size)
        self._dropped = 0
        self._lock = threading.Lock()
        self._metrics = metrics
        self._hists: Dict[str, Histogram] = {}
        self._sids = itertools.count(1)  # next() is atomic in CPython
        self._local = threading.local()  # .stack: this thread's open spans
        # deterministic-test clock: only a VIRTUAL clock's now() is used
        # directly; REAL mode falls back to time.monotonic (wall time can
        # step backwards across NTP slews — a trace must not)
        # ``now`` is the spans' clock, and is read with the tracer off too:
        # a counter that sits beside the spans (a wait in a queue) is taken
        # on it
        if clock is not None and getattr(clock, "mode", None) == "virtual":
            self.now = clock.now
            self.clock_name = "virtual"
        else:
            self.now = time.monotonic
            self.clock_name = "monotonic"

    # -- recording ----------------------------------------------------------
    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    def _open(self, name, parent, req, detached, attrs) -> Span:
        stack = self._stack()
        if parent is None and stack:
            parent = stack[-1]
        if parent is not None:
            psid = parent.sid
            if parent.req is not None:
                req = parent.req
        else:
            psid = None
        span = Span(
            name,
            self.now(),
            threading.get_ident(),
            attrs or None,
            next(self._sids),
            psid,
            req,
        )
        if not detached:
            stack.append(span)
        return span

    def span(self, name: str, parent: Optional[Span] = None, req=None, **attrs):
        """Context manager timing a synchronous phase (``parent`` as for
        ``begin``).  ``end(span, **attrs)`` inside the block ends it with
        what the body learned; leaving the block is then a no-op."""
        if not self.enabled:
            return _NOOP_SCOPE
        return _SpanScope(self, self._open(name, parent, req, False, attrs))

    def begin(
        self,
        name: str,
        parent: Optional[Span] = None,
        req=None,
        detached: bool = False,
        **attrs,
    ) -> Optional[Span]:
        """Open a span explicitly (completes via ``end``).  ``parent`` names
        the cause where it is not the span open on this thread (work handed
        over from another thread); ``detached`` for a span that outlives
        the scope that begins it or that another thread ends.  Returns
        None when disabled — ``end(None)`` is a no-op, so call sites never
        need their own enabled check."""
        if not self.enabled:
            return None
        return self._open(name, parent, req, detached, attrs)

    def current(self) -> Optional[Span]:
        """The innermost span open on this thread: what a caller hands to
        the thread it starts, as ``parent=`` or to ``under``."""
        if not self.enabled:
            return None
        stack = self._stack()
        return stack[-1] if stack else None

    def under(self, parent: Optional[Span]):
        """Context manager for a worker thread: everything this thread
        records inside the block has ``parent`` (a span open on the
        caller's thread) as its cause."""
        if not self.enabled or parent is None:
            return _NOOP_SCOPE
        return _UnderScope(self._stack(), parent)

    def end(self, span: Optional[Span], **attrs) -> None:
        """Complete a span from ``begin`` (None-safe, double-end-safe)."""
        if span is None or span.end is not None:
            return
        span.end = self.now()
        _unwind(self._stack(), span)
        if attrs:
            if span.attrs:
                span.attrs.update(attrs)
            else:
                span.attrs = attrs
        self._complete(span)

    def _complete(self, span: Span) -> None:
        dur_ms = span.duration * 1000.0
        with self._lock:
            if self._idx >= self.ring_size:
                self._dropped += 1
            self._ring[self._idx % self.ring_size] = span
            self._idx += 1
            hist = self._hists.get(span.name)
            if hist is None:
                hist = self._make_hist(span.name)
                self._hists[span.name] = hist
            hist.update(dur_ms)

    def _make_hist(self, name: str) -> Histogram:
        if self._metrics is not None:
            # registered in the shared registry: /metrics reports the
            # trace.<name> aggregate with zero extra plumbing
            return self._metrics.new_histogram("trace." + name)
        return Histogram()

    # -- reading ------------------------------------------------------------
    def _spans_locked(self) -> List[Span]:
        n = min(self._idx, self.ring_size)
        cursor = self._idx % self.ring_size
        if self._idx <= self.ring_size:
            return [s for s in self._ring[:n] if s is not None]
        return [
            s
            for s in self._ring[cursor:] + self._ring[:cursor]
            if s is not None
        ]

    def _aggregates_locked(self) -> Dict[str, dict]:
        return {
            name: {
                "count": h.count,
                "p50_ms": h.percentile(0.5),
                "p95_ms": h.percentile(0.95),
                "max_ms": h.max_value,
            }
            for name, h in sorted(self._hists.items())
        }

    def _clear_locked(self) -> None:
        self._ring = [None] * self.ring_size
        self._idx = 0
        self._dropped = 0
        for h in self._hists.values():
            h.clear()

    def spans(self) -> List[Span]:
        """Completed spans, oldest first (wraparound resolved)."""
        with self._lock:
            return self._spans_locked()

    @property
    def dropped(self) -> int:
        """Spans overwritten by ring wraparound since the last clear."""
        with self._lock:
            return self._dropped

    def aggregates(self) -> Dict[str, dict]:
        """Per-name latency summary: count / p50 / p95 / max, milliseconds."""
        with self._lock:
            return self._aggregates_locked()

    def clear(self) -> None:
        """Drop recorded spans and aggregates (bench: reset after warmup).
        Registry-backed histograms are cleared in place so /metrics stays
        consistent with the ring."""
        with self._lock:
            self._clear_locked()

    def snapshot(self, clear: bool = False):
        """(spans, aggregates, dropped) under ONE lock hold — the /trace
        endpoint's dump-then-maybe-clear must not lose spans completed
        between a separate dump and clear."""
        with self._lock:
            out = (self._spans_locked(), self._aggregates_locked(), self._dropped)
            if clear:
                self._clear_locked()
        return out

    def chrome_trace(self) -> dict:
        """Chrome ``trace_event`` JSON (load via chrome://tracing or
        https://ui.perfetto.dev)."""
        from .chrome import chrome_trace_json

        return chrome_trace_json(self.spans(), clock=self.clock_name)


# Disabled tracer for components constructed without an Application (ops-level
# BatchVerifier benchmarks, unit tests): every record call is a cheap no-op.
NULL_TRACER = Tracer(enabled=False, ring_size=1)


def tracer_of(app) -> Tracer:
    """The app's tracer, or NULL_TRACER for app-less/legacy callers."""
    t = getattr(app, "tracer", None)
    return t if t is not None else NULL_TRACER
