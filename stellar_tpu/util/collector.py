"""The node's schedule for the collector's full passes.

CPython tries a full (generation-2) pass every ``threshold0 x threshold1 x
threshold2`` = 700 x 10 x 10 = 70,000 net container allocations, wherever the
count happens to cross.  A 5,000-tx close allocates three times that, so two
to three full passes landed in the middle of every close, each walking every
live frame, delta and row of the close to free nothing of it.

While an ``Application`` holds the policy (``install`` at
``Application.create``, ``release`` at ``graceful_stop``, counted over every
``Application`` of the process; the interpreter's thresholds come back when
the last one goes):

- generations 0 and 1 run on allocation counts as before; generation 2 never
  fires on a count (its threshold is set beyond any count);
- ``ledger_boundary()`` — the tail of ``LedgerManager.close_ledger``, inside
  ``ledger.close`` — runs a full pass when one is *due*; ``idle_check()``
  hangs the same rule on the overlay's tick for a node that closes nothing.

Due reads the collector's own counters.  ``young`` = ``gc.get_count()[2]``,
the generation-1 passes since the last full pass, each ~7,700 net container
allocations.  A full pass is due when

- ``young >= YOUNG_PASSES_DUE`` (128: about a million net allocations, four
  5,000-tx closes), or
- ``young`` exceeds the interpreter's own threshold2 (10: the interpreter
  would have tried a pass by now) and ``CLOSES_DUE`` (4) ledgers have closed
  since the last full pass.

So cyclic garbage dropped during close n is freed by the boundary of close
n + 4 wherever four closes make more than ten young passes (from ~700
transactions a ledger up; a 1,000-tx node runs a full pass every fourth
close where the interpreter ran one every other), and sooner where ledgers
are wider than 5,000; below that the node runs full passes no more often
than the interpreter's count rule would.  Held uncollected at most: the
net allocations of 128 young passes plus one close's (the check runs at
boundaries only) — under a million container objects, ~150 MB if every one
of them were garbage.  A close of payments leaves none: its frames hold no
cycle (``OperationFrame.parent_tx`` is weak, a cache line that leaves the
entry cache drops its memoized frame) and die with their last holder.

A catch-up that replays a range holds every decoded set of it until its
ledger applies — 61,000 frames of a 64-ledger checkpoint of 1,000-tx ledgers
— and each full pass would walk them all to free none (0.6 s a pass at a
round's start, a fifth of the replay).  ``park()`` moves what is live now
out of the full passes' sight (``gc.freeze``) and ``unpark()`` brings back
what is left of it; reference counts free a parked object as ever, only a
cycle among them waits for ``unpark``.

Every full pass, whoever asked for it, is one ``gc.full`` span in each
holder's tracer (``cause``: ``boundary`` / ``timer`` / ``explicit``;
``collected``, ``uncollectable``), under whatever span is open there, and is
counted in ``stats()`` (``/info`` ``collector``).
"""

from __future__ import annotations

import gc
import time

YOUNG_PASSES_DUE = 128
CLOSES_DUE = 4
# threshold2 while the policy is held: a count of young passes since the
# last full pass that no process reaches (a C int)
_NEVER = (1 << 31) - 1

_holders: list = []  # the holders' tracers
_saved = None  # the interpreter's thresholds, while the policy is held
_cause = "explicit"  # who asked for the pass that runs next
_pass_t0 = 0.0
_pass_spans: list = []  # (tracer, span) of the pass in progress
_stats = {
    "full_passes": 0,
    "full_pass_s": 0.0,
    "boundary_checks": 0,
    "boundary_passes": 0,
    "closes_since_full": 0,
}


def install(tracer) -> None:
    """Take the policy for one ``Application`` (named by its tracer)."""
    global _saved
    if not _holders:
        _saved = gc.get_threshold()
        gc.set_threshold(_saved[0], _saved[1], _NEVER)
        gc.callbacks.append(_on_pass)
    _holders.append(tracer)


def release(tracer) -> None:
    """Give the policy back (a no-op for a tracer that does not hold it)."""
    global _saved
    if tracer not in _holders:
        return
    _holders.remove(tracer)
    if not _holders:
        gc.set_threshold(*_saved)
        _saved = None
        gc.callbacks.remove(_on_pass)


def held() -> bool:
    return bool(_holders)


def stats() -> dict:
    return dict(_stats)


def ledger_boundary() -> None:
    """A ledger has closed: run the full pass if it is due."""
    if not _holders:
        return
    _stats["boundary_checks"] += 1
    _stats["closes_since_full"] += 1
    if _due():
        _stats["boundary_passes"] += 1
        _collect("boundary")


def idle_check() -> None:
    """The same rule for a node that closes nothing, from a timer."""
    if _holders and _due():
        _collect("timer")


def park() -> None:
    """Leave everything live now out of the full passes until ``unpark``."""
    gc.freeze()


def unpark() -> None:
    """Give the parked objects back to the full passes (all of them,
    whoever parked them: a second holder's then cost a pass again)."""
    gc.unfreeze()


def _due() -> bool:
    young = gc.get_count()[2]
    return young >= YOUNG_PASSES_DUE or (
        young > _saved[2] and _stats["closes_since_full"] >= CLOSES_DUE
    )


def _collect(cause: str) -> None:
    global _cause
    _cause = cause
    try:
        gc.collect()
    finally:
        _cause = "explicit"


def _on_pass(phase: str, info: dict) -> None:
    global _pass_t0
    if info["generation"] != 2:
        return
    if phase == "start":
        _pass_t0 = time.monotonic()
        for tracer in _holders:
            _pass_spans.append((tracer, tracer.begin("gc.full", cause=_cause)))
        return
    _stats["full_passes"] += 1
    _stats["full_pass_s"] += time.monotonic() - _pass_t0
    _stats["closes_since_full"] = 0
    for tracer, span in _pass_spans:
        tracer.end(
            span,
            collected=info["collected"],
            uncollectable=info["uncollectable"],
        )
    _pass_spans.clear()
