"""``backlog``: a closed loop that keeps a fixed number of pre-signed
payments pending at the admission edge, on the node's normal path.

Each payment is decoded from its envelope bytes, as ``/tx`` decodes a client's
blob, and enters through ``IngestPlane.submit_sync`` (the path behind ``/tx``
and ``/generateload``); each ledger goes herder -> SCP nomination and
ballot -> externalize -> close, on the real clock.  A reading is one ledger
cycle: replenish what the last ledger took (one new payment from each
account whose payment closed), trigger the next ledger, crank the node until
it has closed.  The trigger is the harness's (``Herder.trigger_next_ledger``,
what ``/manualclose`` calls) the moment the last close ends, so no cadence
timer sets the pace.  Shape of the reference's ``[autoload]``
(``src/simulation/CoreTests.cpp:294``) and ``profile_system.py autoload``.

Parameters: ``pending_widths`` — tx-set widths kept pending;
``ceiling_tx_per_s`` — the rate set-up pre-signs enough payments for;
``balance``, ``amount`` — stroops.
"""

from __future__ import annotations

import math
import time

from benchmarks import node as N
from benchmarks.stats import Reading

PENDING = "PENDING"
SLIP_ROOM_S = 30.0


class Workload(N.NodeWorkload):
    def __init__(self, ctx):
        self.ctx = ctx
        p = ctx.traffic["params"]
        self.width = N.width_of(ctx.config, ctx.rehearsal)
        n_accounts = N.accounts_of(ctx.config, ctx.rehearsal)
        self.pending_target = int(p["pending_widths"]) * self.width
        if self.pending_target > n_accounts:
            raise SystemExit("tx-backlog: more pending payments than accounts")
        cfg = N.make_config(ctx.config, ctx.work, ctx.rehearsal, ctx.traffic.get("node"))
        self.node = N.Node(cfg, self.width)
        self.keys = N.keys_from_seed(ctx.seed, n_accounts)
        self.node.fund(self.keys, p["balance"])
        self.amount = int(p["amount"])
        self.index = {k.public_raw: i for i, k in enumerate(self.keys)}
        per_account = int(
            p["rehearsal_per_account"]
            if ctx.rehearsal
            else math.ceil(p["ceiling_tx_per_s"] * (ctx.seconds + 15) / self.pending_target)
        )
        self.next_seq = [self.node.first_seq(k) for k in self.keys]
        self.queues = [[] for _ in self.keys]
        for i in range(self.pending_target):
            self.queues[i] = [self._sign(i) for _ in range(per_account)]
        self.signed_late = 0
        self.offered = 0
        self.refused = 0
        self.closetime_waits = 0
        self.node.app.start()
        self.herder = self.node.app.herder
        self.ingest = self.node.app.ingest
        self._to_submit = list(range(self.pending_target))

    def _sign(self, i: int):
        # Every account pays its fixed partner (the accounts themselves are
        # made from the seed), so the accounts a ledger touches fall into
        # groups of two whatever the seed, as in ``closes``.  Partners drawn
        # at random put 1,000 payments on 2,000 accounts, the density at
        # which a giant group appears in the apply partition: the seed then
        # changes the work (one ``close.apply`` of 3.2 s among 190 ms ones,
        # seed 812, my chip run, PR 23; none in 22 runs with fixed partners).
        tx = self.node.payment(self.keys[i], self.next_seq[i], self.keys[i ^ 1], self.amount)
        self.next_seq[i] += 1
        return tx.envelope.to_xdr()

    def _take(self, i: int, in_window: bool):
        if not self.queues[i]:
            self.queues[i].append(self._sign(i))
            self.signed_late += 1
        return self.queues[i].pop(0)

    def step(self, in_window: bool) -> Reading:
        node, ctx = self.node, self.ctx
        blobs = [self._take(i, in_window) for i in self._to_submit]
        t0 = time.monotonic()
        txs = node.frames(blobs)
        t0b = time.monotonic()
        submit = self.ingest.submit_sync
        for tx in txs:
            if submit(tx) != PENDING:
                self.refused += 1
        t1 = time.monotonic()
        self.offered += len(txs)
        # closeTime is whole seconds and strictly increasing, so ledgers
        # closing faster than one a second run ahead of the clock; the herder
        # refuses a value more than MAX_TIME_SLIP_SECONDS ahead.  Never
        # reached at 1,000 tx a ledger today (counted if it is).
        ahead = node.lm.last_closed.header.scpValue.closeTime - time.time()
        if ahead > SLIP_ROOM_S:
            self.closetime_waits += 1
            time.sleep(ahead - SLIP_ROOM_S)
        t2 = self._close_one()
        t3 = time.monotonic()
        ctx.span("bench.decode", t0, t0b, txs=len(txs))
        ctx.span("bench.submit", t0b, t1, txs=len(txs))
        ctx.span("bench.trigger", t1, t2)
        ctx.span("bench.crank", t2, t3)
        del txs
        took = [tx.envelope.tx.sourceAccount.value for ld, _ in node._unsettled for tx in ld.tx_set.transactions]
        node.settle()
        self._to_submit = [self.index[a] for a in took]
        return Reading(t0, t3, len(took))

    def _close_one(self) -> float:
        """Trigger the next ledger and crank the node until it has closed;
        returns when the trigger returned."""
        node = self.node
        seq = node.lm.get_last_closed_ledger_num()
        self.herder.trigger_next_ledger(node.lm.get_ledger_num())
        triggered = time.monotonic()
        while node.lm.get_last_closed_ledger_num() == seq:
            node.clock.crank(False)
            if time.monotonic() > triggered + 120.0:
                raise RuntimeError("the node closed no ledger in 120 s")
        return triggered

    def finish(self) -> None:
        """Drain: close ledgers until nothing is pending."""
        super().finish()
        for _ in range(8):
            if self.herder.num_pending_txs() == 0:
                return
            self._close_one()
            self.node.settle()

    def check(self, check) -> tuple:
        check.compare("submissions_refused", self.refused, 0)
        check.compare("left_pending_after_drain", self.herder.num_pending_txs(), 0)
        attempted, failed = super().check(check)
        return attempted, failed + self.refused

    def notes(self) -> dict:
        return {
            "signed_in_window": self.signed_late,
            "refused": self.refused,
            "closetime_waits": self.closetime_waits,
        }
