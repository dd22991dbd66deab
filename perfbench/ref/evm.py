"""A plain interpreter of the opcodes a contract of plain storage
arithmetic uses (``ref/contracts.py``'s Smallbank), with UPSTREAM's gas:
go-ethereum v1.8.2, the Byzantium instruction set.  One stack, one memory,
storage as a dict, no nested call, no log.  Nothing of the program.

Upstream: ``core/vm/jump_table.go`` (which opcode costs which step, its
stack's needs), ``core/vm/gas_table.go`` (``memoryGasCost``, ``gasSha3``,
``gasSStore``, ``gasMStore``, ``gasReturn``, ``gasRevert``),
``core/vm/instructions.go``, ``core/vm/interpreter.go`` (``Run``: the
constant gas and the dynamic gas are taken before the operation),
``core/state_transition.go`` (``IntrinsicGas``, ``TransitionDb``,
``refundGas``).  The constants carry ``params/protocol_params.go``'s and
``core/vm/gas.go``'s names.
"""

from __future__ import annotations

U256 = 1 << 256

# core/vm/gas.go
GasQuickStep, GasFastestStep, GasFastStep = 2, 3, 5
GasMidStep, GasSlowStep, GasExtStep = 8, 10, 20
# params/protocol_params.go
TxGas = 21_000
TxDataZeroGas, TxDataNonZeroGas = 4, 68
Sha3Gas, Sha3WordGas = 30, 6
SloadGasEIP150 = 200           # params/gas_table.go GasTableEIP158.SLoad
SstoreSetGas, SstoreResetGas, SstoreClearGas = 20_000, 5_000, 5_000
SstoreRefundGas = 15_000
JumpdestGas = 1
MemoryGas, QuadCoeffDiv = 3, 512
StackLimit = 1024

# opcode -> (name, items popped, items pushed, constant gas): what
# jump_table.go's Byzantium set gives the opcodes implemented here.  An
# opcode whose cost has a dynamic part (memory, SHA3's words, SSTORE)
# carries its constant part alone; ``run`` works the rest out.
OPS = {
    0x00: ("STOP", 0, 0, 0),
    0x01: ("ADD", 2, 1, GasFastestStep),
    0x02: ("MUL", 2, 1, GasFastStep),
    0x03: ("SUB", 2, 1, GasFastestStep),
    0x04: ("DIV", 2, 1, GasFastStep),
    0x06: ("MOD", 2, 1, GasFastStep),
    0x10: ("LT", 2, 1, GasFastestStep),
    0x11: ("GT", 2, 1, GasFastestStep),
    0x14: ("EQ", 2, 1, GasFastestStep),
    0x15: ("ISZERO", 1, 1, GasFastestStep),
    0x16: ("AND", 2, 1, GasFastestStep),
    0x17: ("OR", 2, 1, GasFastestStep),
    0x19: ("NOT", 1, 1, GasFastestStep),
    0x20: ("SHA3", 2, 1, Sha3Gas),
    0x33: ("CALLER", 0, 1, GasQuickStep),
    0x34: ("CALLVALUE", 0, 1, GasQuickStep),
    0x35: ("CALLDATALOAD", 1, 1, GasFastestStep),
    0x36: ("CALLDATASIZE", 0, 1, GasQuickStep),
    0x50: ("POP", 1, 0, GasQuickStep),
    0x51: ("MLOAD", 1, 1, GasFastestStep),
    0x52: ("MSTORE", 2, 0, GasFastestStep),
    0x54: ("SLOAD", 1, 1, SloadGasEIP150),
    0x55: ("SSTORE", 2, 0, 0),
    0x56: ("JUMP", 1, 0, GasMidStep),
    0x57: ("JUMPI", 2, 0, GasSlowStep),
    0x5B: ("JUMPDEST", 0, 0, JumpdestGas),
    0xF3: ("RETURN", 2, 0, 0),
    0xFD: ("REVERT", 2, 0, 0),
}
for _n in range(1, 33):
    OPS[0x5F + _n] = (f"PUSH{_n}", 0, 1, GasFastestStep)
for _n in range(1, 17):
    OPS[0x7F + _n] = (f"DUP{_n}", _n, _n + 1, GasFastestStep)
    OPS[0x8F + _n] = (f"SWAP{_n}", _n + 1, _n + 1, GasFastestStep)
BY_NAME = {name: op for op, (name, _i, _o, _g) in OPS.items()}


class Fault(Exception):
    """Out of gas, a bad jump, a stack that is too short or too long, an
    opcode that is not one: the frame's gas is gone and nothing it wrote
    stays."""


class Result:
    def __init__(self, status: int, gas_left: int, output: bytes,
                 refund: int, writes: dict):
        self.status = status      # 1 ran to its end, 0 reverted or failed
        self.gas_left = gas_left
        self.output = output
        self.refund = refund      # the refund counter (0 unless status 1)
        self.writes = writes      # slot -> value, {} unless status 1


def memory_gas(words: int) -> int:
    """``memoryGasCost``'s total for a memory of ``words`` words."""
    return words * MemoryGas + words * words // QuadCoeffDiv


def jumpdests(code: bytes) -> set:
    """``codeBitmap``'s complement: the JUMPDESTs that are no PUSH's
    data."""
    out, pc = set(), 0
    while pc < len(code):
        op = code[pc]
        if op == 0x5B:
            out.add(pc)
        pc += op - 0x5E if 0x60 <= op <= 0x7F else 1
    return out


def run(code: bytes, data: bytes, storage, gas: int, keccak, *,
        caller: int = 0, value: int = 0) -> Result:
    """``code`` on call data ``data`` with ``gas``; ``storage`` answers
    ``.get(slot, 0)`` and is not written: what the frame wrote comes back
    in the result.  ``keccak`` is the hash SHA3 uses (bytes -> 32 bytes)."""
    stack: list = []
    mem = bytearray()
    writes: dict = {}
    refund = 0
    dests = jumpdests(code)
    pc = 0

    def reach(offset: int, size: int) -> tuple:
        """``(gas, words)`` of a memory that reaches ``offset + size``
        (no gas, no growth for no bytes)."""
        if size == 0:
            return 0, 0
        words = (offset + size + 31) // 32
        have = len(mem) // 32
        if words <= have:
            return 0, 0
        return memory_gas(words) - memory_gas(have), words

    try:
        while True:
            op = code[pc] if pc < len(code) else 0x00
            if op not in OPS:
                raise Fault(f"invalid opcode {op:#x}")
            name, pops, pushes, cost = OPS[op]
            if len(stack) < pops:
                raise Fault("stack underflow")
            if len(stack) - pops + pushes > StackLimit:
                raise Fault("stack limit reached")
            # the dynamic part, known from the stack before the operation
            if name == "SHA3":
                cost += Sha3WordGas * ((stack[-2] + 31) // 32)
            if name in ("SHA3", "RETURN", "REVERT"):
                mem_args = (stack[-1], stack[-2])
            elif name in ("MLOAD", "MSTORE"):
                mem_args = (stack[-1], 32)
            else:
                mem_args = None
            if name == "SSTORE":
                slot, new = stack[-1], stack[-2]
                old = writes.get(slot)
                if old is None:
                    old = storage.get(slot, 0)
                if old == 0 and new != 0:
                    cost += SstoreSetGas
                elif old != 0 and new == 0:
                    cost += SstoreClearGas
                    refund += SstoreRefundGas
                else:
                    cost += SstoreResetGas
            if gas < cost:
                raise Fault("out of gas")
            gas -= cost
            if mem_args is not None:
                more, words = reach(*mem_args)
                if gas < more:
                    raise Fault("out of gas")
                gas -= more
                mem.extend(bytes(32 * words - len(mem)) if words else b"")
            pc += 1

            if name == "STOP":
                return Result(1, gas, b"", refund, writes)
            if name.startswith("PUSH"):
                n = op - 0x5F
                stack.append(int.from_bytes(
                    code[pc:pc + n].ljust(n, b"\0"), "big"))
                pc += n
            elif name.startswith("DUP"):
                stack.append(stack[-(op - 0x7F)])
            elif name.startswith("SWAP"):
                n = op - 0x8F
                stack[-1], stack[-1 - n] = stack[-1 - n], stack[-1]
            elif name == "ADD":
                stack.append((stack.pop() + stack.pop()) % U256)
            elif name == "MUL":
                stack.append(stack.pop() * stack.pop() % U256)
            elif name == "SUB":
                a, b = stack.pop(), stack.pop()
                stack.append((a - b) % U256)
            elif name == "DIV":
                a, b = stack.pop(), stack.pop()
                stack.append(a // b if b else 0)
            elif name == "MOD":
                a, b = stack.pop(), stack.pop()
                stack.append(a % b if b else 0)
            elif name == "LT":
                a, b = stack.pop(), stack.pop()
                stack.append(int(a < b))
            elif name == "GT":
                a, b = stack.pop(), stack.pop()
                stack.append(int(a > b))
            elif name == "EQ":
                stack.append(int(stack.pop() == stack.pop()))
            elif name == "ISZERO":
                stack.append(int(stack.pop() == 0))
            elif name == "AND":
                stack.append(stack.pop() & stack.pop())
            elif name == "OR":
                stack.append(stack.pop() | stack.pop())
            elif name == "NOT":
                stack.append(U256 - 1 - stack.pop())
            elif name == "SHA3":
                off, n = stack.pop(), stack.pop()
                stack.append(int.from_bytes(
                    keccak(bytes(mem[off:off + n])), "big"))
            elif name == "CALLER":
                stack.append(caller)
            elif name == "CALLVALUE":
                stack.append(value)
            elif name == "CALLDATALOAD":
                off = stack.pop()
                stack.append(int.from_bytes(
                    data[off:off + 32].ljust(32, b"\0"), "big"))
            elif name == "CALLDATASIZE":
                stack.append(len(data))
            elif name == "POP":
                stack.pop()
            elif name == "MLOAD":
                off = stack.pop()
                stack.append(int.from_bytes(mem[off:off + 32], "big"))
            elif name == "MSTORE":
                off, v = stack.pop(), stack.pop()
                mem[off:off + 32] = v.to_bytes(32, "big")
            elif name == "SLOAD":
                slot = stack.pop()
                v = writes.get(slot)
                stack.append(storage.get(slot, 0) if v is None else v)
            elif name == "SSTORE":
                slot, v = stack.pop(), stack.pop()
                writes[slot] = v
            elif name == "JUMP":
                pc = stack.pop()
                if pc not in dests:
                    raise Fault("invalid jump destination")
            elif name == "JUMPI":
                to, cond = stack.pop(), stack.pop()
                if cond:
                    if to not in dests:
                        raise Fault("invalid jump destination")
                    pc = to
            elif name == "JUMPDEST":
                pass
            elif name == "RETURN":
                off, n = stack.pop(), stack.pop()
                return Result(1, gas, bytes(mem[off:off + n]), refund,
                              writes)
            elif name == "REVERT":
                # the gas that is left goes back; the writes and the
                # refund counter do not stay (evm.Call: RevertToSnapshot)
                off, n = stack.pop(), stack.pop()
                return Result(0, gas, bytes(mem[off:off + n]), 0, {})
    except Fault:
        return Result(0, 0, b"", 0, {})


def intrinsic_gas(data: bytes) -> int:
    """``IntrinsicGas`` of a message call with ``data``."""
    zeros = data.count(0)
    return (TxGas + TxDataZeroGas * zeros
            + TxDataNonZeroGas * (len(data) - zeros))


def apply_call(code: bytes, data: bytes, storage, gas_limit: int, keccak,
               caller: int = 0):
    """``TransitionDb`` of a call to the contract at gas price 0 and value
    0: ``(status, gas used, writes)`` or None where the gas limit is under
    the intrinsic gas (the block is invalid).  The refund is capped at
    half of what was used (``refundGas``)."""
    intrinsic = intrinsic_gas(data)
    if gas_limit < intrinsic:
        return None
    res = run(code, data, storage, gas_limit - intrinsic, keccak,
              caller=caller)
    used = gas_limit - res.gas_left
    used -= min(res.refund, used // 2)
    return res.status, used, res.writes
