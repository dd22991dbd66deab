"""BLOCKBENCH's Smallbank, three times: the contract's BYTECODE, assembled
here by hand (there is no Solidity compiler in the tree), with each
routine's line of ``benchmark/contracts/ethereum/smallbank.sol`` beside
it; the same six procedures as plain Python on two dicts
(:class:`Bank`); and the call data that asks for each.  The generator
(``perfbench/gen_contracts.py``) holds the three to each other at set-up:
the bytecode under ``ref/evm.py`` leaves the slots the plain Python
leaves.  Nothing of the program.

Departures from ``smallbank.sol``, each under ``assumed`` in the
configuration's file: customer ids are 32-byte words where the source
takes ``string`` (a mapping's slot is ``keccak(id || index)`` either way,
one SHA3 a lookup); ``sendPayment`` REVERTs where the payer's checking
balance is under the amount (OLTPBench's SendPayment aborts there; the
source subtracts unchecked), and it does so AFTER the payee's credit is
stored, so that an abort has a write to undo: the guarantee "a call that
aborts leaves no write" is about that write; ``writeCheck`` takes
OLTPBench's branch (the penalty of 1 where the total is under the amount).
The arithmetic is the source's: unchecked, modulo 2**256.
"""

from __future__ import annotations

from perfbench.ref.evm import BY_NAME
from perfbench.ref.keccak import keccak256, keccak256_many

SAVING, CHECKING = 0, 1          # the two mappings' slots, in source order
U256 = 1 << 256

SIGNATURES = {
    "almagate": "almagate(bytes32,bytes32)",
    "getBalance": "getBalance(bytes32)",
    "updateBalance": "updateBalance(bytes32,uint256)",
    "updateSaving": "updateSaving(bytes32,uint256)",
    "sendPayment": "sendPayment(bytes32,bytes32,uint256)",
    "writeCheck": "writeCheck(bytes32,uint256)",
}
SELECTOR = {name: keccak256(sig.encode())[:4]
            for name, sig in SIGNATURES.items()}


def assemble(program: list) -> bytes:
    """``program``: opcode names, ``("PUSH", value)`` (the shortest PUSH
    that holds it), ``("PUSH4", value)`` (that width), ``("LABEL", name)``
    (a JUMPDEST) and ``("TO", name)`` (PUSH2 of a label's offset)."""
    sized, at, labels = [], 0, {}
    for item in program:
        if isinstance(item, str):
            enc = bytes([BY_NAME[item]])
        elif item[0] == "LABEL":
            labels[item[1]] = at
            enc = bytes([BY_NAME["JUMPDEST"]])
        elif item[0] == "TO":
            enc = item                      # two bytes, filled in below
        else:
            n = int(item[0][4:] or max(1, (item[1].bit_length() + 7) // 8))
            enc = bytes([BY_NAME[f"PUSH{n}"]]) + item[1].to_bytes(n, "big")
        sized.append(enc)
        at += 3 if isinstance(enc, tuple) else len(enc)
    return b"".join(
        bytes([BY_NAME["PUSH2"]]) + labels[e[1]].to_bytes(2, "big")
        if isinstance(e, tuple) else e for e in sized)


def _slot(arg: int, mapping: int) -> list:
    """``mapping[argN]``'s slot on the stack: keccak(arg || index)."""
    return [("PUSH", 4 + 32 * arg), "CALLDATALOAD", ("PUSH", 0), "MSTORE",
            ("PUSH", mapping), ("PUSH", 32), "MSTORE",
            ("PUSH", 64), ("PUSH", 0), "SHA3"]


def _arg(n: int) -> list:
    return [("PUSH", 4 + 32 * n), "CALLDATALOAD"]


def smallbank_program() -> list:
    p: list = [
        # the selector: calldata's first word over 2**224 (solc 0.4's)
        ("PUSH", 0), "CALLDATALOAD", ("PUSH", 1 << 224), "SWAP1", "DIV"]
    for name in SIGNATURES:
        p += ["DUP1", ("PUSH4", int.from_bytes(SELECTOR[name], "big")),
              "EQ", ("TO", name), "JUMPI"]
    p += [("LABEL", "abort"), ("PUSH", 0), "DUP1", "REVERT"]

    p += [("LABEL", "almagate"),
          # uint bal1 = savingStore[arg0];
          *_slot(0, SAVING), "SLOAD",
          # uint bal2 = checkingStore[arg1];
          *_slot(1, CHECKING), "SLOAD", "ADD",
          # checkingStore[arg0] = 0;
          ("PUSH", 0), *_slot(0, CHECKING), "SSTORE",
          # savingStore[arg1] = bal1 + bal2;
          *_slot(1, SAVING), "SSTORE", "STOP"]

    p += [("LABEL", "getBalance"),
          # uint bal1 = savingStore[arg0]; uint bal2 = checkingStore[arg0];
          *_slot(0, SAVING), "SLOAD", *_slot(0, CHECKING), "SLOAD",
          # balance = bal1 + bal2; return balance;
          "ADD", ("PUSH", 0), "MSTORE", ("PUSH", 32), ("PUSH", 0),
          "RETURN"]

    for name, mapping in (("updateBalance", CHECKING),
                          ("updateSaving", SAVING)):
        p += [("LABEL", name),
              # uint bal1 = <mapping>[arg0]; uint bal2 = arg1;
              *_slot(0, mapping), "SLOAD", *_arg(1),
              # <mapping>[arg0] = bal1 + bal2;
              "ADD", *_slot(0, mapping), "SSTORE", "STOP"]

    p += [("LABEL", "sendPayment"),
          # uint bal1 = checkingStore[arg0];
          *_slot(0, CHECKING), "SLOAD",
          # uint bal2 = checkingStore[arg1]; uint amount = arg2;
          *_slot(1, CHECKING), "SLOAD", *_arg(2),
          # bal2 += amount; checkingStore[arg1] = bal2;
          "SWAP1", "DUP2", "ADD", *_slot(1, CHECKING), "SSTORE",
          # (OLTPBench: insufficient funds abort) require(bal1 >= amount)
          "DUP1", "DUP3", "LT", ("TO", "abort"), "JUMPI",
          # bal1 -= amount; checkingStore[arg0] = bal1;
          "SWAP1", "SUB", *_slot(0, CHECKING), "SSTORE", "STOP"]

    p += [("LABEL", "writeCheck"),
          # uint bal1 = checkingStore[arg0]; uint bal2 = savingStore[arg0];
          *_slot(0, CHECKING), "SLOAD", *_slot(0, SAVING), "SLOAD",
          # uint amount = arg1;  if (bal1 + bal2 < amount)
          *_arg(1), "SWAP1", "DUP3", "ADD", "DUP2", "SWAP1", "LT",
          ("TO", "penalty"), "JUMPI",
          # else checkingStore[arg0] = bal1 - amount;
          "SWAP1", "SUB", ("TO", "store"), "JUMP",
          # checkingStore[arg0] = bal1 - amount - 1;
          ("LABEL", "penalty"), "SWAP1", "SUB", ("PUSH", 1), "SWAP1", "SUB",
          ("LABEL", "store"), *_slot(0, CHECKING), "SSTORE", "STOP"]
    return p


SMALLBANK = assemble(smallbank_program())


def call_data(name: str, *args: int) -> bytes:
    """The selector and each argument as a 32-byte word."""
    return SELECTOR[name] + b"".join(a.to_bytes(32, "big") for a in args)


def slot_of(customer: int, mapping: int) -> int:
    """The storage slot of ``mapping[customer]``."""
    return int.from_bytes(keccak256(slot_preimage(customer, mapping)), "big")


def slot_preimage(customer: int, mapping: int) -> bytes:
    return customer.to_bytes(32, "big") + mapping.to_bytes(32, "big")


def slots_of(customers, mapping: int) -> list:
    """``slot_of`` for many customers, their Keccaks taken together."""
    return [int.from_bytes(h, "big") for h in keccak256_many(
        slot_preimage(c, mapping) for c in customers)]


class Aborted(Exception):
    """The procedure aborted: nothing it wrote stays."""


class Bank:
    """The six procedures on two dicts (customer id -> balance; an id
    that is not there has 0, and a balance that becomes 0 leaves)."""

    def __init__(self, saving: dict, checking: dict):
        self.saving, self.checking = saving, checking

    @staticmethod
    def _put(book: dict, customer: int, value: int) -> None:
        if value:
            book[customer] = value
        else:
            book.pop(customer, None)

    def almagate(self, a0: int, a1: int) -> None:
        total = (self.saving.get(a0, 0) + self.checking.get(a1, 0)) % U256
        self._put(self.checking, a0, 0)
        self._put(self.saving, a1, total)

    def getBalance(self, a0: int) -> int:
        return (self.saving.get(a0, 0) + self.checking.get(a0, 0)) % U256

    def updateBalance(self, a0: int, amount: int) -> None:
        self._put(self.checking, a0,
                  (self.checking.get(a0, 0) + amount) % U256)

    def updateSaving(self, a0: int, amount: int) -> None:
        self._put(self.saving, a0, (self.saving.get(a0, 0) + amount) % U256)

    def sendPayment(self, a0: int, a1: int, amount: int) -> None:
        bal1, bal2 = self.checking.get(a0, 0), self.checking.get(a1, 0)
        if bal1 < amount:
            raise Aborted("insufficient funds")
        # the payee's credit first, then the payer's debit (of what was
        # read before either), as the source's two locals are stored
        self._put(self.checking, a1, (bal2 + amount) % U256)
        self._put(self.checking, a0, bal1 - amount)

    def writeCheck(self, a0: int, amount: int) -> None:
        bal1, bal2 = self.checking.get(a0, 0), self.saving.get(a0, 0)
        fee = 1 if (bal1 + bal2) % U256 < amount else 0
        self._put(self.checking, a0, (bal1 - amount - fee) % U256)
