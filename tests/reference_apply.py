"""A reference apply by plain arithmetic (ROADMAP A11).

It shares nothing with ``stellar_tpu/ledger`` or ``stellar_tpu/tx``: a ledger
is ``{account: [balance, seq]}`` and a fee pool, a transaction is a source, a
sequence number, a fee and operations that are native payments or
create-accounts.  The rules are the protocol's, as the program's source
(stellar-core ``TransactionFrame.cpp``, ``PaymentOpFrame.cpp``,
``CreateAccountOpFrame.cpp``) states them:

- a close charges every transaction's fee (all the account has, where it
  has less) and takes its sequence number, in apply order, before any
  transaction is applied;
- a transaction whose source could not pay the fee once more above its
  reserve is ``txINSUFFICIENT_BALANCE``; otherwise its operations apply in
  order, every one of them even after one has failed, and where one failed
  the transaction is ``txFAILED`` and none of its operations' effects stay;
- a payment needs its destination (``PAYMENT_NO_DESTINATION``) and leaves
  the source its reserve (``PAYMENT_UNDERFUNDED``); a payment to oneself
  succeeds and moves nothing;
- a created account is not its creator (``CREATE_ACCOUNT_MALFORMED``), must
  not exist (``CREATE_ACCOUNT_ALREADY_EXIST``), starts
  with at least the reserve (``CREATE_ACCOUNT_LOW_RESERVE``), leaves the
  source its reserve (``CREATE_ACCOUNT_UNDERFUNDED``), and starts at sequence
  number ``ledger_seq << 32``;
- at admission a transaction is refused whose sequence number is not its
  account's next (``txBAD_SEQ``), whose source does not exist
  (``txNO_ACCOUNT``) or whose fee is under the base fee an operation
  (``txINSUFFICIENT_FEE``).

The apply order of a set is an input: consensus fixes it (``TxSetFrame
.sort_for_apply``), the apply path only follows it."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple


@dataclass(frozen=True)
class Tx:
    source: str
    seq: int
    fee: int
    # ("pay", destination, amount) | ("create", destination, starting balance)
    ops: Tuple[Tuple[str, str, int], ...]


class Ledger:
    def __init__(self, accounts: Dict[str, Sequence[int]], base_fee: int, base_reserve: int, fee_pool: int = 0):
        self.accounts = {name: [balance, seq] for name, (balance, seq) in accounts.items()}
        self.base_fee = base_fee
        self.reserve = 2 * base_reserve  # an account with no sub-entries
        self.fee_pool = fee_pool

    def admit(self, tx: Tx) -> str:
        """The code a node's front door gives the transaction, alone."""
        if tx.fee < self.base_fee * len(tx.ops):
            return "txINSUFFICIENT_FEE"
        if tx.source not in self.accounts:
            return "txNO_ACCOUNT"
        balance, seq = self.accounts[tx.source]
        if seq + 1 != tx.seq:
            return "txBAD_SEQ"
        if balance - tx.fee < self.reserve:
            return "txINSUFFICIENT_BALANCE"
        return "txSUCCESS"

    def close(self, ledger_seq: int, txs: Sequence[Tx]) -> List[Tuple[str, List[str]]]:
        """Apply ``txs``, given in apply order -> (code, operation codes) of
        each; operation codes only where the operations ran."""
        for tx in txs:
            account = self.accounts[tx.source]
            if account[1] + 1 != tx.seq:
                raise ValueError(f"{tx.source}: sequence {tx.seq} after {account[1]}")
            fee = min(tx.fee, account[0])
            account[0] -= fee
            account[1] = tx.seq
            self.fee_pool += fee
        return [self._apply(ledger_seq, tx) for tx in txs]

    def _apply(self, ledger_seq: int, tx: Tx) -> Tuple[str, List[str]]:
        if tx.fee < self.base_fee * len(tx.ops):
            return "txINSUFFICIENT_FEE", []
        if self.accounts[tx.source][0] - tx.fee < self.reserve:
            return "txINSUFFICIENT_BALANCE", []
        kept = {name: list(state) for name, state in self.accounts.items()}
        codes = [self._operation(ledger_seq, tx.source, *op) for op in tx.ops]
        if all(code.endswith("_SUCCESS") for code in codes):
            return "txSUCCESS", codes
        self.accounts = kept
        return "txFAILED", codes

    def _operation(self, ledger_seq: int, source: str, kind: str, dest: str, amount: int) -> str:
        accounts = self.accounts
        if kind == "pay":
            if dest == source:
                return "PAYMENT_SUCCESS"
            if dest not in accounts:
                return "PAYMENT_NO_DESTINATION"
            if accounts[source][0] - amount < self.reserve:
                return "PAYMENT_UNDERFUNDED"
            accounts[source][0] -= amount
            accounts[dest][0] += amount
            return "PAYMENT_SUCCESS"
        assert kind == "create", kind
        if dest == source:
            return "CREATE_ACCOUNT_MALFORMED"
        if dest in accounts:
            return "CREATE_ACCOUNT_ALREADY_EXIST"
        if amount < self.reserve:
            return "CREATE_ACCOUNT_LOW_RESERVE"
        if accounts[source][0] - self.reserve < amount:
            return "CREATE_ACCOUNT_UNDERFUNDED"
        accounts[source][0] -= amount
        accounts[dest] = [amount, ledger_seq << 32]
        return "CREATE_ACCOUNT_SUCCESS"
