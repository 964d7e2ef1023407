"""Controls: the timed path, broken underneath, so that ``correct`` must come
out false.  Run by ``--control <name>`` (never by the benchmark's own runs);
``benchmarks/tests`` keeps one per generator, and PERF.md records the chip
runs.  Each breaks one guarantee the configuration states.

* ``accept-invalid`` (flushes): the verifier answers True for one lane it
  would refuse — a degraded verifier (signatures guarantee).
* ``refuse-valid`` (flushes): the verifier answers False for one valid lane.
* ``drop-tx`` (node cells): apply leaves out one transaction of every set —
  the ledger hash no longer equals the plain node's (determinism guarantee).
* ``deferred-commit`` (node cells): the close's SQL COMMIT is held back and
  issued only when the database is closed at the node's stop, as a change
  that batches commits out of the close would do: when the last timed close
  returns, a fresh reader of the file does not find it (durability
  guarantee).  A check made after the stop would pass.
"""

from __future__ import annotations


def _flip(wl, want: bool) -> None:
    verifier = wl.backend._verifier
    inner = verifier.verify

    def verify(items):
        out = inner(items)
        for i, ok in enumerate(out):
            if ok != want:
                out[i] = want
                break
        return out

    verifier.verify = verify


def _drop_tx(wl) -> None:
    lm = wl.node.lm
    inner = lm._apply_transactions

    def apply(txs, ledger_delta, tx_result_set):
        return inner(txs[:-1], ledger_delta, tx_result_set)

    lm._apply_transactions = apply


class _DeferredCommits:
    """The database's connection with every COMMIT held back until
    ``close``: one long transaction that swallows the BEGINs after the
    first."""

    def __init__(self, raw):
        self._raw = raw
        self._open = False

    def execute(self, sql, *params):
        if sql == "COMMIT":
            return None
        if sql == "BEGIN":
            if self._open:
                return None
            self._open = True
        return self._raw.execute(sql, *params)

    def close(self):
        if self._open:
            self._raw.execute("COMMIT")
        self._raw.close()

    def __getattr__(self, name):
        return getattr(self._raw, name)


def _deferred_commit(wl) -> None:
    db = wl.node.app.database
    db._conn = _DeferredCommits(db._conn)


CONTROLS = {
    "accept-invalid": lambda wl: _flip(wl, True),
    "refuse-valid": lambda wl: _flip(wl, False),
    "drop-tx": _drop_tx,
    "deferred-commit": _deferred_commit,
}


def apply(name: str, wl) -> None:
    if name not in CONTROLS:
        raise SystemExit(f"no control {name!r}; have {sorted(CONTROLS)}")
    CONTROLS[name](wl)
