"""``closes``: consecutive full transaction sets, each validated and closed
through the node's own close path.

A reading is one close: the set is handed to validation
(``TxSetFrame.check_valid``, where the signature flush runs) and then
externalized (``LedgerManager.externalize_value``: close pipeline, apply,
bucket list, SQL commit), as a validator does with a peer's set.  The sets
are built and signed in set-up, from the seed, with signatures the node has
never seen; if a run closes more than were prepared, more are built between
readings and counted (``built_in_window``).

Parameters (the traffic file): ``floor_close_s_per_tx`` — the fastest close,
per transaction, that set-up prepares sets for (window / (floor x width),
plus the warm-up's); ``balance`` — stroops an account starts with;
``amount`` — stroops a payment moves.
"""

from __future__ import annotations

import math
import time

from benchmarks import node as N
from benchmarks.stats import Reading


class Workload(N.NodeWorkload):

    def __init__(self, ctx):
        self.ctx = ctx
        p = ctx.traffic["params"]
        self.width = N.width_of(ctx.config, ctx.rehearsal)
        n_accounts = N.accounts_of(ctx.config, ctx.rehearsal)
        cfg = N.make_config(ctx.config, ctx.work, ctx.rehearsal, ctx.traffic.get("node"))
        if n_accounts < 2 * self.width:
            raise SystemExit("full-ledgers: a set needs 2 x width distinct accounts")
        self.node = N.Node(cfg, self.width)
        self.keys = N.keys_from_seed(ctx.seed, n_accounts)
        self.node.fund(self.keys, p["balance"])
        self.amount = int(p["amount"])
        self.round = 0
        self._next_seq: dict = {}
        self.built_in_window = 0
        self.offered = 0
        self._sets = []
        prebuilt = int(
            p["rehearsal_sets"]
            if ctx.rehearsal
            else math.ceil(ctx.seconds / (p["floor_close_s_per_tx"] * self.width)) + 6
        )
        for _ in range(prebuilt):
            self._sets.append(self._build())

    def _build(self) -> list:
        """One round of payments: every round a new pairing of sources and
        destinations drawn from the seed, ``width`` distinct sources."""
        order = N.permutation(self.ctx.seed, len(self.keys), self.round)
        src, dst = order[: self.width], order[self.width : 2 * self.width]
        txs = []
        for s, d in zip(src, dst):
            key = self.keys[s]
            seq = self._next_seq.get(s, self.node.first_seq(key))
            self._next_seq[s] = seq + 1
            txs.append(self.node.payment(key, seq, self.keys[d], self.amount).envelope.to_xdr())
        self.round += 1
        return txs

    def step(self, in_window: bool) -> Reading:
        if not self._sets:
            self._sets.append(self._build())
            if in_window:
                self.built_in_window += 1
        node = self.node
        txs = node.frames(self._sets.pop(0))
        ledger_data = node.ledger_data(txs)
        t0 = time.monotonic()
        if not ledger_data.tx_set.check_valid(node.app):
            raise RuntimeError("a payment set did not validate")
        node.lm.externalize_value(ledger_data)
        t1 = time.monotonic()
        self.offered += len(txs)
        del ledger_data, txs
        node.settle()
        return Reading(t0, t1, self.width)

    def notes(self) -> dict:
        return {"built_in_window": self.built_in_window, "sets_left": len(self._sets)}
