"""One standalone validator, built from a configuration file, and the data
its cells feed it: funded accounts and signed native payments made from the
seed.  The builders follow ``bench.py``'s (accounts pre-created 100 to a
transaction, ``maxTxSetSize`` raised by a ledger upgrade in the first closed
value), which follow the reference's ``LedgerPerformanceTests.cpp:149-225``.

Everything the node closes is recorded (``Node.closed``) so that the plain
reference can be fed the same transaction sets after the window.

Prepared transactions are kept as XDR bytes and decoded when they are used,
and what was closed is recorded as bytes again outside the reading.  A
``TransactionFrame`` and the operation frames and results that apply hangs on
it form reference cycles: held by the harness (or made immortal by
``gc.freeze()``) they kept ~17,000 objects per 1,000-tx close alive, and the
collector's full passes, every other close, grew from 26 ms to 450 ms over 40
closes (PERF.md, Findings, PR 23).  Bytes are invisible to the collector.
"""

from __future__ import annotations

import hashlib
import os
import random
from typing import Dict, List, NamedTuple, Optional


class Closed(NamedTuple):
    seq: int
    envelopes: list  # the set's TransactionEnvelopes as XDR bytes
    value: object  # StellarValue
    hash: bytes  # the node's ledger hash after the close


PASSPHRASE = "stellar-tpu benchmark network ; %s"


def make_config(cfg_file: dict, work: str, rehearsal: bool, overrides: Optional[dict] = None):
    """The program's ``Config`` at its shipped defaults, with the
    configuration file's ``node`` settings, the cell's traffic overrides
    and the deployment's paths laid over it."""
    from stellar_tpu.crypto.keys import SecretKey
    from stellar_tpu.main.config import Config
    from stellar_tpu.xdr.scp import SCPQuorumSet

    cfg = Config()
    node = dict(cfg_file["node"])
    if rehearsal:
        node.update(cfg_file.get("rehearsal", {}).get("node", {}))
    node.update(overrides or {})
    for k, v in node.items():
        if not hasattr(cfg, k):
            raise SystemExit(f"configuration names an unknown Config field {k!r}")
        setattr(cfg, k, v)
    cfg.NETWORK_PASSPHRASE = PASSPHRASE % cfg_file["name"]
    cfg.DATABASE = f"sqlite3://{work}/node.db"
    cfg.BUCKET_DIR_PATH = os.path.join(work, "buckets")
    cfg.TMP_DIR_PATH = os.path.join(work, "tmp")
    cfg.HTTP_PORT = 0
    cfg.NODE_SEED = SecretKey.from_seed(hashlib.sha256(b"bench node " + cfg_file["name"].encode()).digest())
    cfg.QUORUM_SET = SCPQuorumSet(1, [cfg.NODE_SEED.get_public_key()], [])
    return cfg


def width_of(cfg_file: dict, rehearsal: bool) -> int:
    if rehearsal:
        return int(cfg_file["rehearsal"]["width"])
    return int(cfg_file["node"]["DESIRED_MAX_TX_PER_LEDGER"])


def accounts_of(cfg_file: dict, rehearsal: bool) -> int:
    if rehearsal:
        return int(cfg_file["rehearsal"]["accounts"])
    return int(cfg_file["accounts"])


def keys_from_seed(seed: int, n: int, label: bytes = b"acct") -> list:
    from stellar_tpu.crypto.keys import SecretKey

    return [
        SecretKey.from_seed(hashlib.sha256(b"bench %s %d %d" % (label, seed, i)).digest())
        for i in range(n)
    ]


def tx_frame(network_id: bytes, fee: int, source, seq: int, ops: list):
    """A signed single-signature transaction (as ``tx/testutils.tx_from_ops``)."""
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
    frame = TransactionFrame(network_id, X.TransactionEnvelope(tx, []))
    frame.add_signature(source)
    return frame


def payment_op(dest, amount: int):
    import stellar_tpu.xdr as X

    return X.Operation(
        None,
        X.OperationBody(
            X.OperationType.PAYMENT,
            X.PaymentOp(dest.get_public_key(), X.Asset.native(), amount),
        ),
    )


class Node:
    """The validator under test plus the record of what it closed."""

    def __init__(self, cfg, width: int):
        from stellar_tpu.main.application import Application
        from stellar_tpu.util.clock import REAL_TIME, VirtualClock

        self.cfg = cfg
        self.width = width
        self.clock = VirtualClock(REAL_TIME)
        self.app = Application.create(self.clock, cfg, new_db=True)
        self.lm = self.app.ledger_manager
        self.closed: List[Closed] = []
        self.fee = self.lm.get_tx_fee()
        self.genesis_balance = self.lm.last_closed.header.totalCoins
        inner = self.lm.close_ledger
        self._unsettled: list = []

        def close_ledger(ledger_data):
            inner(ledger_data)
            self._unsettled.append((ledger_data, self.lm.last_closed.hash))

        self.lm.close_ledger = close_ledger
        self.created_at: Dict[bytes, int] = {}
        self._stopped = False

    # -- data ---------------------------------------------------------------
    def _tx(self, source, seq: int, ops: list):
        return tx_frame(self.app.network_id, self.fee, source, seq, ops)

    def payment(self, source, seq: int, dest, amount: int):
        return self._tx(source, seq, [payment_op(dest, amount)])

    def settle(self) -> List[Closed]:
        """Record what was closed since the last call as bytes, and let go
        of the frames.  Called outside readings."""
        new = []
        for ledger_data, ledger_hash in self._unsettled:
            new.append(
                Closed(
                    ledger_data.ledger_seq,
                    [tx.envelope.to_xdr() for tx in ledger_data.tx_set.transactions],
                    ledger_data.value,
                    ledger_hash,
                )
            )
        self._unsettled.clear()
        self.closed.extend(new)
        return new

    def frames(self, blobs: list) -> list:
        """Transaction frames from envelope bytes, as a node decodes what a
        peer or a client sends."""
        import stellar_tpu.xdr as X
        from stellar_tpu.tx.frame import TransactionFrame

        nid = self.app.network_id
        return [TransactionFrame(nid, X.TransactionEnvelope.from_xdr(b)) for b in blobs]

    def first_seq(self, key) -> int:
        """The first sequence number an account created by ``fund`` can use."""
        return (self.created_at[key.public_raw] << 32) + 1

    def fund(self, keys: list, balance: int) -> None:
        """Create ``keys`` from the root account, 100 to a transaction, and
        raise ``maxTxSetSize`` to the configuration's width in the first
        closed value.  Set-up: closed through ``close_ledger`` directly."""
        import stellar_tpu.xdr as X
        from stellar_tpu.crypto.keys import SecretKey
        from stellar_tpu.ledger.accountframe import AccountFrame
        from stellar_tpu.xdr.base import xdr_to_opaque
        from stellar_tpu.xdr.ledger import LedgerUpgrade, LedgerUpgradeType

        root = SecretKey.from_seed(self.app.network_id)
        seq = AccountFrame.load_account(root.get_public_key(), self.app.database).get_seq_num()
        upgrades = [
            xdr_to_opaque(
                LedgerUpgrade(LedgerUpgradeType.LEDGER_UPGRADE_MAX_TX_SET_SIZE, self.width)
            )
        ]
        per_ledger = 100 * min(100, self.lm.get_max_tx_set_size())
        for start in range(0, len(keys), per_ledger):
            batch = keys[start : start + per_ledger]
            txs = []
            for i in range(0, len(batch), 100):
                seq += 1
                ops = [
                    X.Operation(
                        None,
                        X.OperationBody(
                            X.OperationType.CREATE_ACCOUNT,
                            X.CreateAccountOp(k.get_public_key(), balance),
                        ),
                    )
                    for k in batch[i : i + 100]
                ]
                txs.append(self._tx(root, seq, ops))
            ledger_data = self.ledger_data(txs, upgrades)
            upgrades = []
            if not ledger_data.tx_set.check_valid(self.app):
                raise RuntimeError("account-creation set does not validate")
            self.lm.close_ledger(ledger_data)
            self.settle()
            for k in batch:
                self.created_at[k.public_raw] = self.lm.last_closed.header.ledgerSeq
        if self.lm.get_max_tx_set_size() != self.width:
            raise RuntimeError("maxTxSetSize upgrade did not take")

    def ledger_data(self, txs: list, upgrades=()):
        """A transaction set on the last closed ledger, as a peer would
        hand it over, with the value that externalizes it."""
        from stellar_tpu.herder.ledgerclose import LedgerCloseData
        from stellar_tpu.herder.txset import TxSetFrame
        from stellar_tpu.xdr.ledger import StellarValue

        txset = TxSetFrame(self.lm.last_closed.hash, txs)
        txset.sort_for_hash()
        value = StellarValue(
            txset.get_contents_hash(),
            self.lm.last_closed.header.scpValue.closeTime + 5,
            list(upgrades),
            0,
        )
        return LedgerCloseData(self.lm.current.header.ledgerSeq, txset, value)

    # -- what the layer metrics read -------------------------------------------
    def counters(self) -> dict:
        out = {"sig_backend": self.app.sig_backend.stats()}
        if self.app.ingest is not None:
            out["ingest"] = self.app.ingest.stats()
        out["applied_tx"] = self.app.metrics.new_meter(("ledger", "transaction", "count"), "tx").count
        return out

    def drain_spans(self) -> list:
        spans, _, dropped = self.app.tracer.snapshot(clear=True)
        if dropped:
            raise RuntimeError(f"the program's span ring dropped {dropped} spans")
        return spans

    def stop(self) -> None:
        if not self._stopped:
            self._stopped = True
            self.app.graceful_stop()
            self.clock.shutdown()


def permutation(seed: int, n: int, salt: int) -> List[int]:
    order = list(range(n))
    random.Random((seed << 8) ^ salt).shuffle(order)
    return order


class NodeWorkload:
    """What the node cells share: counters, spans, and the comparison with
    the plain reference after the window."""

    node: Node
    ctx = None
    offered = 0  # transactions offered to the node since funding

    def counters(self) -> dict:
        return self.node.counters()

    def drain_spans(self) -> list:
        return self.node.drain_spans()

    def notes(self) -> dict:
        return {}

    def db_path(self) -> str:
        return self.node.cfg.DATABASE[len("sqlite3://") :]

    def finish(self) -> None:
        """Called as the window closes.  The durability guarantee is held
        here, the moment the last timed close has returned and before
        anything stops or flushes the node: a fresh reader of the database
        file has to find that close."""
        from benchmarks import reference as ref

        node = self.node
        self._at_close = (
            node.lm.last_closed.header.ledgerSeq,
            node.lm.last_closed.hash.hex(),
            sum(len(ld.tx_set.transactions) for ld, _ in node._unsettled)
            + sum(len(c.envelopes) for c in node.closed),
            ref.durable_state(self.db_path(), balances=False),
        )

    def close(self) -> None:
        self.node.stop()

    def check(self, check) -> tuple:
        """-> (attempted, failed).  attempted: transactions offered;
        failed: offered and not in a closed ledger, plus closes whose hash
        differs from the reference's."""
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
        funding_txs = in_closed - self.applied_payments(closed)
        root = SecretKey.from_seed(node.app.network_id)
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
        node.stop()
        missing = max(0, self.offered + funding_txs - durable["txhistory"])
        check.compare("txs_not_in_txhistory", missing, 0, f"{durable['txhistory']} rows after the drain")

        want = ref.replay_hashes(
            closed, self.ctx.config, node.cfg.NETWORK_PASSPHRASE, self.ctx.work
        )
        bad = sum(1 for c, h in zip(closed, want) if c.hash != h) + max(0, len(closed) - len(want))
        check.compare("ledger_hashes_differing", bad, 0, f"of {len(closed)} closes")

        expect = ref.expected_balances(
            closed, genesis, node.fee, lambda pk: PubKeyUtils.to_strkey(pk)
        )
        off = sum(1 for k, v in expect.items() if durable["balances"].get(k) != v)
        off += sum(1 for k in durable["balances"] if k not in expect)
        check.compare("balances_off_plain_arithmetic", off, 0, f"of {len(expect)} accounts")
        return self.offered, missing + bad

    def applied_payments(self, closed: list) -> int:
        return sum(
            1
            for c in closed
            for e in c.envelopes
            if e.tx.operations[0].body.value.__class__.__name__ == "PaymentOp"
        )
