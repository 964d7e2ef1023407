"""``multisig_closes``: the ``closes`` traffic over accounts that are all held
under weighted signers — consecutive full transaction sets of native
payments, every envelope signed by several of its source's signers and not
by its master key, each set validated and closed through the node's own close
path (``closes``'s ``step``).

After ``fund`` and before the first set is built, every account installs its
signers through closed ledgers: one transaction of ``signers_per_account``
SET_OPTIONS operations signed by the master key, each adding one signer of
weight 1 with a key of the account's own, the last also setting the master's
weight and the three thresholds (the configuration's ``assumed``).  From then
on an envelope carries ``signatures_per_tx`` signatures: a seeded choice of
the source's signers, in seeded order.

The check keeps every row of ``NodeWorkload.check`` and adds the plain
reference's (``benchmarks/reference_multisig.py``): the authorisation of every
closed payment by plain arithmetic over the signer table built here, against
the result codes in the node's ``txhistory``; and the node's ``signers``
table, read by sqlite3 alone, against that same table.

``commit.flush``'s row counts are attributes of the program's span, and
``benchmarks/spans.compact`` keeps the attributes of two other span names
only; ``drain_spans`` therefore repeats them on a span of the harness's own,
``bench.flush_rows`` (of no length, at the flush's end, so that it is never
the innermost span of an idle gap), for ``signer_rows_per_close`` to read.
"""

from __future__ import annotations

import math
import random

from benchmarks import node as N
from benchmarks import reference_multisig as RM
from benchmarks.generators import closes


def unsigned_frame(network_id: bytes, fee: int, source, seq: int, ops: list):
    """``node.tx_frame`` without the master key's signature."""
    import stellar_tpu.xdr as X
    from stellar_tpu.tx.frame import TransactionFrame

    tx = X.Transaction(
        sourceAccount=source.get_public_key(),
        fee=fee * max(1, len(ops)),
        seqNum=seq,
        timeBounds=None,
        memo=X.Memo.none(),
        operations=ops,
        ext=0,
    )
    return TransactionFrame(network_id, X.TransactionEnvelope(tx, []))


class Workload(closes.Workload):
    signers = None  # per account, its signers' SecretKeys; None until installed

    def _install_signers(self) -> None:
        """Set-up: give every account its signers, through closed ledgers."""
        import stellar_tpu.xdr as X

        cfg_file, node = self.ctx.config, self.node
        per = int(cfg_file["width"]["signers_per_account"])
        self.per_tx = int(cfg_file["width"]["signatures_per_tx"])
        held = cfg_file["assumed"]
        thresholds = (held["lowThreshold"], held["medThreshold"], held["highThreshold"])
        keys = N.keys_from_seed(self.ctx.seed, per * len(self.keys), b"signer")
        self.signers = [keys[i * per : (i + 1) * per] for i in range(len(self.keys))]
        self.held = {
            k.public_raw: RM.Account(
                tuple((s.public_raw, 1) for s in mine), held["masterWeight"], held["medThreshold"]
            )
            for k, mine in zip(self.keys, self.signers)
        }

        def options(signer, last: bool):
            return X.Operation(
                None,
                X.OperationBody(
                    X.OperationType.SET_OPTIONS,
                    X.SetOptionsOp(
                        masterWeight=held["masterWeight"] if last else None,
                        lowThreshold=thresholds[0] if last else None,
                        medThreshold=thresholds[1] if last else None,
                        highThreshold=thresholds[2] if last else None,
                        signer=X.Signer(signer.get_public_key(), 1),
                    ),
                ),
            )

        txs = []
        for i, (key, mine) in enumerate(zip(self.keys, self.signers)):
            seq = node.first_seq(key)
            self._next_seq[i] = seq + 1
            ops = [options(s, j == per - 1) for j, s in enumerate(mine)]
            txs.append(N.tx_frame(node.app.network_id, node.fee, key, seq, ops))
        # in sets of one size, each at most a ledger and at most a device
        # batch: a set's single-signature flush then pads to the bucket the
        # window's flushes use, and set-up compiles no bucket of its own
        ledgers = math.ceil(len(txs) / min(self.width, node.cfg.SIG_BATCH_MAX))
        size = math.ceil(len(txs) / ledgers)
        for start in range(0, len(txs), size):
            ledger_data = node.ledger_data(txs[start : start + size])
            if not ledger_data.tx_set.check_valid(node.app):
                raise RuntimeError("a signer set-up set did not validate")
            node.lm.close_ledger(ledger_data)
            node.settle()

    def _build(self) -> list:
        """One round of payments, as ``closes`` pairs them, each signed by
        a seeded choice of its source's signers in seeded order."""
        if self.signers is None:
            self._install_signers()
        node = self.node
        order = N.permutation(self.ctx.seed, len(self.keys), self.round)
        src, dst = order[: self.width], order[self.width : 2 * self.width]
        pick = random.Random((self.ctx.seed << 8) ^ self.round ^ 0x5153)
        txs = []
        for s, d in zip(src, dst):
            seq = self._next_seq[s]
            self._next_seq[s] = seq + 1
            frame = unsigned_frame(
                node.app.network_id, node.fee, self.keys[s], seq, [N.payment_op(self.keys[d], self.amount)]
            )
            for signer in pick.sample(self.signers[s], self.per_tx):
                frame.add_signature(signer)
            txs.append(frame.envelope.to_xdr())
        self.round += 1
        return txs

    def drain_spans(self) -> list:
        spans = super().drain_spans()
        for s in spans:
            if s.name == "commit.flush" and s.attrs and "signer_rows" in s.attrs:
                self.ctx.span(
                    "bench.flush_rows", s.end, s.end,
                    signer_rows=s.attrs["signer_rows"], account_rows=s.attrs.get("account_rows"),
                )
        return spans

    def check(self, check) -> tuple:
        """``NodeWorkload.check`` row for row, with the balances' plain
        arithmetic extended to SET_OPTIONS, then the reference's two rows.
        -> (attempted, failed): failed also counts the transactions whose
        authorisation differs."""
        from benchmarks import reference as ref
        from stellar_tpu.crypto.keys import PubKeyUtils, SecretKey

        import stellar_tpu.xdr as X

        node = self.node
        node.settle()
        closed = [
            c._replace(envelopes=[X.TransactionEnvelope.from_xdr(b) for b in c.envelopes])
            for c in node.closed
        ]
        inv = node.app.invariants.dump_info()
        check.compare("invariant_violations", int(inv.get("total_violations", 0)), 0)
        check.compare(
            "closes_not_invariant_checked",
            max(0, len(closed) - int(inv.get("closes_checked", 0))),
            0,
        )
        in_closed = sum(len(c.envelopes) for c in closed)
        setup_txs = in_closed - self.applied_payments(closed)
        network_id = node.app.network_id
        root = SecretKey.from_seed(network_id)
        genesis = {root.get_strkey_public(): node.genesis_balance}

        # durability, as the window closed (taken in ``finish``) ...
        lcl_seq, lcl_hash, closed_txs, then = self._at_close
        check.compare("durable_lcl_seq_behind", lcl_seq - (then["top"] or 0), 0, "as the last timed close returned")
        check.compare("durable_lcl_hash_differs", 0 if then["lcl"] == lcl_hash else 1, 0, f"lcl {lcl_seq}")
        check.compare(
            "closed_txs_not_yet_in_txhistory", max(0, closed_txs - then["txhistory"]), 0,
            f"{then['txhistory']} rows as the last timed close returned",
        )
        # ... and after the drain, still before the node stops
        durable = ref.durable_state(self.db_path())
        stored = RM.result_codes(self.db_path())
        strkey = lambda raw: PubKeyUtils.to_strkey(X.PublicKey.from_ed25519(raw))  # noqa: E731
        rows_off = RM.signer_rows_off(
            self.db_path(),
            {strkey(k): {strkey(pk): w for pk, w in a.signers} for k, a in self.held.items()},
        )
        node.stop()
        missing = max(0, self.offered + setup_txs - durable["txhistory"])
        check.compare("txs_not_in_txhistory", missing, 0, f"{durable['txhistory']} rows after the drain")

        want = ref.replay_hashes(closed, self.ctx.config, node.cfg.NETWORK_PASSPHRASE, self.ctx.work)
        bad = sum(1 for c, h in zip(closed, want) if c.hash != h) + max(0, len(closed) - len(want))
        check.compare("ledger_hashes_differing", bad, 0, f"of {len(closed)} closes")

        expect = RM.expected_balances(closed, genesis, node.fee, PubKeyUtils.to_strkey)
        off = sum(1 for k, v in expect.items() if durable["balances"].get(k) != v)
        off += sum(1 for k in durable["balances"] if k not in expect)
        check.compare("balances_off_plain_arithmetic", off, 0, f"of {len(expect)} accounts")

        payments = RM.txs_of(closed, self.held)
        differs = RM.authorisation_differs(RM.expected_codes(payments, self.held, network_id), stored)
        check.compare(
            "txs_authorisation_differs", differs, 0,
            f"of {len(payments)} closed payments, {sum(len(e.signatures) for e in payments)} signatures",
        )
        check.compare("signer_rows_off", rows_off, 0, f"of {sum(len(a.signers) for a in self.held.values())} rows")
        return self.offered, missing + bad + differs
