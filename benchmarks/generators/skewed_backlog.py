"""``skewed_backlog``: ``backlog``'s closed loop over accounts that are not
drawn evenly — a few send and receive most of every ledger, so a set holds
per-account sequence chains.

Set-up draws one seeded stream of (source, destination) pairs from the
configuration's ``skew`` (zipfian: P(rank k) ∝ k^-constant over the accounts;
rank -> account by a permutation made, like the accounts' keys, from the
configuration's ``hot_set_seed``, so the hot accounts are scattered over the
key space and are the same in every run; the destination drawn independently
and redrawn while it equals the source) and signs it in order, each source's
payments with consecutive sequence numbers from ``Node.first_seq``.  A cycle offers the
next *k* envelopes of the stream, *k* = what the last ledger closed, so the
backlog stays at ``pending_widths`` sets and an account's payments always
arrive in sequence order.  The stream never depends on the clock or on which
transactions closed: a run that outlasts what set-up signed draws on from the
same generator (counted, ``signed_in_window``).  Everything else — decode,
``IngestPlane.submit_sync``, the trigger the moment the last close ends, the
crank, the drain, the slip gate, the reading — is ``backlog.Workload``'s own
``step``, which is handed the stream's next envelope where it asks for an
account's.

After the window the node is held to ``benchmarks/reference_skew.py`` beside
``NodeWorkload.check``'s rows: sequence numbers and balances by plain
arithmetic over the stored envelopes, the protocol's apply order, gapless
per-account prefixes of the stream, and the shape the timed window closed.

What the program records of chains is on span attributes ``spans.compact``
drops, so ``drain_spans`` repeats them on spans of the harness's own, each of
no length at the end of the span it repeats: ``bench.set_chains`` (the
proposed set's ``accounts`` / ``longest_chain``, from the ``txset.validate``
that walked it), ``bench.surge_cut`` (``herder.surge``'s ``cut``),
``bench.apply_order`` (``txset.sort_for_apply``'s ``accounts`` / ``batches``).
``counters`` adds the herder's ``tx_queue`` block.  On a program without them
(the parent commit) nothing is repeated and the readers return nothing.
"""

from __future__ import annotations

import bisect
import itertools
import math
import random

from benchmarks import node as N
from benchmarks import reference_skew as RS
from benchmarks.generators import backlog
from benchmarks.stats import Reading

REPEATS = {
    # program span -> (the harness's repeat, the attributes it carries)
    "txset.validate": ("bench.set_chains", ("accounts", "longest_chain")),
    "herder.surge": ("bench.surge_cut", ("cut",)),
    "txset.sort_for_apply": ("bench.apply_order", ("accounts", "batches")),
}


class Workload(backlog.Workload):
    def __init__(self, ctx):  # noqa: D107 - replaces backlog's set-up whole
        self.ctx = ctx
        p = ctx.traffic["params"]
        skew = ctx.config["skew"]
        if skew["distribution"] != "zipfian" or not skew["scrambled"]:
            raise SystemExit("skewed-backlog: the one skew this generator draws is scrambled zipfian")
        self.width = N.width_of(ctx.config, ctx.rehearsal)
        n = N.accounts_of(ctx.config, ctx.rehearsal)
        self.pending_target = int(p["pending_widths"]) * self.width
        cfg = N.make_config(ctx.config, ctx.work, ctx.rehearsal, ctx.traffic.get("node"))
        self.node = N.Node(cfg, self.width)
        # who the accounts are, and so which of them is hot and where its id
        # falls in the surge filter's order, is the configuration's; the
        # run's seed chooses the draws alone (README.skew.md: the seed decision)
        hot_set = int(ctx.config["hot_set_seed"])
        self.keys = N.keys_from_seed(hot_set, n)
        self.node.fund(self.keys, p["balance"])
        self.amount = int(p["amount"])
        self.constant = float(skew["constant"])
        # rank k (0 is the hottest) is account by_rank[k]
        self.by_rank = N.permutation(hot_set, n, 0x5A)
        self._cum = list(itertools.accumulate((k + 1) ** -self.constant for k in range(n)))
        self._rng = random.Random((ctx.seed << 8) ^ 0x21BF)
        self.next_seq = [self.node.first_seq(k) for k in self.keys]
        self.stream: list = []  # every envelope signed, in the order offered
        self.cursor = 0
        self._extend(
            int(p["rehearsal_stream_widths"]) * self.width
            if ctx.rehearsal
            else math.ceil(p["ceiling_tx_per_s"] * (ctx.seconds + 15))
        )
        self.signed_late = 0
        self.offered = 0
        self.refused = 0
        self.closetime_waits = 0
        self.window_ledgers = None  # first and last ledger closed inside the window
        self.seen: dict = {}
        self.node.app.start()
        self.herder = self.node.app.herder
        self.ingest = self.node.app.ingest
        self.index = {k.public_raw: i for i, k in enumerate(self.keys)}
        self._to_submit = list(range(self.pending_target))

    def _draw(self) -> int:
        k = bisect.bisect_left(self._cum, self._rng.random() * self._cum[-1])
        return self.by_rank[min(k, len(self.by_rank) - 1)]

    def _extend(self, count: int) -> None:
        """The next ``count`` payments of the stream, signed."""
        for _ in range(count):
            s = d = self._draw()
            while d == s:
                d = self._draw()
            tx = self.node.payment(self.keys[s], self.next_seq[s], self.keys[d], self.amount)
            self.next_seq[s] += 1
            self.stream.append(tx.envelope.to_xdr())

    def _take(self, i: int, in_window: bool) -> bytes:
        """The stream's next envelope, whoever ``backlog`` asks it for."""
        if self.cursor == len(self.stream):
            self._extend(1)
            self.signed_late += 1
        self.cursor += 1
        return self.stream[self.cursor - 1]

    def step(self, in_window: bool) -> Reading:
        # ``backlog``'s cycle: it offers one envelope for each transaction
        # the last ledger closed, and ``_take`` hands it the stream's next
        reading = super().step(in_window)
        if in_window:
            lcl = self.node.lm.get_last_closed_ledger_num()
            self.window_ledgers = (self.window_ledgers[0] if self.window_ledgers else lcl, lcl)
        return reading

    # -- what the layer metrics read --------------------------------------------
    def counters(self) -> dict:
        out = self.node.counters()
        stats = getattr(self.herder, "tx_queue_stats", None)
        if stats is not None:  # a program without the counters has no block
            out["tx_queue"] = stats()
        return out

    def drain_spans(self) -> list:
        spans = super().drain_spans()
        for s in spans:
            a = s.attrs
            if a and s.name in REPEATS:
                name, keys = REPEATS[s.name]
                if all(k in a for k in keys):
                    self.ctx.span(name, s.end, s.end, **{k: a[k] for k in keys})
        return spans

    # -- the comparison ------------------------------------------------------------
    def check(self, check) -> tuple:
        from stellar_tpu.crypto.keys import SecretKey

        attempted, failed = super().check(check)
        node = self.node
        root = SecretKey.from_seed(node.app.network_id).public_raw
        last = node.closed[-1].seq if node.closed else 0
        rows, detail, self.seen = RS.compare(
            self.db_path(), {root: (node.genesis_balance, 0)}, self.stream[: self.cursor],
            [self.keys[i].public_raw for i in self.by_rank], self.constant, self.pending_target,
            self.window_ledgers or (last + 1, last),
        )
        for name in RS.ROWS:
            check.compare(name, rows[name], 0, detail[name])
        return attempted, failed

    def notes(self) -> dict:
        out = super().notes()
        sources = {b[4:36] for b in self.stream[: self.cursor]}
        out.update(
            stream_signed=len(self.stream), stream_offered=self.cursor, stream_sources=len(sources),
            window_ledgers=self.window_ledgers, window_shape=self.seen,
        )
        return out
