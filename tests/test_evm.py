"""EVM subset tests: create/call, gas metering, storage, precompiles,
revert semantics (ref role: core/vm/* — gas_table.go, contracts.go,
evm.go Call/Create paths)."""

import pytest

from eges_tpu.core import rlp
from eges_tpu.core.evm import (
    EVM, BlockCtx, intrinsic_gas, G_TX, G_SLOAD, G_SSTORE_SET,
)
from eges_tpu.core.state import (
    Account, StateDB, apply_txn, contract_address, process_block,
)
from eges_tpu.core.types import Transaction
from eges_tpu.crypto.keccak import keccak256
from tests.test_trie_native import trie_rung  # noqa: F401 (a fixture)

# every case on both rungs of the persistent trie: the library's node
# store and the Python nodes (tests/test_trie_native.py old_library)
pytestmark = pytest.mark.usefixtures("trie_rung")

A = b"\xaa" * 20
B = b"\xbb" * 20
COINBASE = b"\xcc" * 20
ETH = 10**18


def st(balance=10 * ETH):
    return StateDB.from_alloc({A: balance})


def run_code(state, code, *, value=0, data=b"", gas=1_000_000):
    """Install ``code`` at B and call it from A."""
    state.set_code(B, bytes(code))
    e = EVM(state, BlockCtx(coinbase=COINBASE, number=7, time=99))
    res = e.call(A, B, value, data, gas)
    return e, res


# -- interpreter basics ---------------------------------------------------

def test_arithmetic_and_return():
    # PUSH1 2, PUSH1 3, MUL, PUSH1 0, MSTORE, PUSH1 32, PUSH1 0, RETURN
    code = bytes.fromhex("6002600302600052602060" + "00f3")
    s = st()
    _, res = run_code(s, code)
    assert res.success
    assert int.from_bytes(res.output, "big") == 6


def test_storage_roundtrip_and_root_changes():
    # SSTORE slot1 = 0x2a; SLOAD slot1; MSTORE; RETURN 32
    code = bytes.fromhex("602a600155600154600052602060 00f3".replace(" ", ""))
    s = st()
    root_before = s.root()
    _, res = run_code(s, code)
    assert res.success
    assert int.from_bytes(res.output, "big") == 0x2A
    assert s.storage_at(B, 1) == 0x2A
    assert s.root() != root_before
    # the account RLP commits to a non-empty storage root
    acct = s.account(B)
    assert acct.storage_root() != Account().storage_root()


def test_revert_rolls_back_storage_and_reports_data():
    # SSTORE slot0=1; PUSH1 0 PUSH1 0 REVERT
    code = bytes.fromhex("6001600055600060 00fd".replace(" ", ""))
    s = st()
    _, res = run_code(s, code)
    assert not res.success
    assert s.storage_at(B, 0) == 0


def test_out_of_gas_consumes_all_and_reverts():
    code = bytes.fromhex("6001600055")  # SSTORE costs 20k
    s = st()
    _, res = run_code(s, code, gas=1000)
    assert not res.success
    assert res.gas_used == 1000
    assert s.storage_at(B, 0) == 0


def test_gas_metering_exact_for_simple_sequence():
    # PUSH1(3) PUSH1(3) ADD(3) POP(2) STOP -> 11 gas
    code = bytes.fromhex("6001600201 50 00".replace(" ", ""))
    s = st()
    _, res = run_code(s, code, gas=1_000)
    assert res.success
    assert res.gas_used == 3 + 3 + 3 + 2


def test_create_then_call_contract():
    """Full txn path: create a counter contract, then call it twice."""
    s = st()
    # runtime: SLOAD(0) 1 ADD DUP1 SSTORE(0) MSTORE(0) RETURN32
    runtime = bytes.fromhex("600054600101806000556000526020 6000f3".replace(" ", ""))
    # init: CODECOPY(runtime) ... RETURN runtime
    n = len(runtime)
    init = bytes([0x60, n, 0x60, 0x0C, 0x60, 0x00, 0x39,  # CODECOPY dst=0 src=12 len=n
                  0x60, n, 0x60, 0x00, 0xF3]) + runtime   # RETURN 0..n
    assert len(init) == 12 + n
    create = Transaction(nonce=0, gas_price=1, gas_limit=500_000,
                         to=None, value=0, payload=init)
    r1 = apply_txn(s, create, A, COINBASE, 0)
    assert r1.status == 1
    caddr = contract_address(A, 0)
    assert s.code(caddr) == runtime

    call = Transaction(nonce=1, gas_price=1, gas_limit=200_000,
                       to=caddr, value=0)
    r2 = apply_txn(s, call, A, COINBASE, r1.cumulative_gas_used)
    assert r2.status == 1
    assert s.storage_at(caddr, 0) == 1
    r3 = apply_txn(s, Transaction(nonce=2, gas_price=1, gas_limit=200_000,
                                  to=caddr), A, COINBASE,
                   r2.cumulative_gas_used)
    assert r3.status == 1
    assert s.storage_at(caddr, 0) == 2
    # fees: coinbase got exactly the gas burned
    burned = r3.cumulative_gas_used
    assert s.balance(COINBASE) == burned


def test_failed_txn_still_charges_gas_and_bumps_nonce():
    s = st()
    s.set_code(B, bytes.fromhex("fe"))  # INVALID opcode
    txn = Transaction(nonce=0, gas_price=1, gas_limit=100_000, to=B,
                      value=ETH)
    bal0 = s.balance(A)
    r = apply_txn(s, txn, A, COINBASE, 0)
    assert r.status == 0
    assert s.nonce(A) == 1
    assert s.balance(B) == 0  # value transfer reverted
    assert s.balance(A) == bal0 - r.cumulative_gas_used  # gas burned
    assert r.cumulative_gas_used == 100_000  # all gas consumed on EvmError


def test_logs_in_receipts():
    # PUSH1 42 PUSH1 0 MSTORE; topic PUSH1 7; LOG1 off=0 len=32
    code = bytes.fromhex("602a600052 6007 6020 6000 a1 00".replace(" ", ""))
    s = st()
    e, res = run_code(s, code)
    assert res.success
    assert len(e.logs) == 1
    addr, topics, data = e.logs[0]
    assert addr == B
    assert topics == ((7).to_bytes(32, "big"),)
    assert int.from_bytes(data, "big") == 42
    # receipts carry and re-encode logs
    from eges_tpu.core.state import Receipt
    rc = Receipt(status=1, cumulative_gas_used=21_000, logs=tuple(e.logs))
    back = Receipt.from_rlp(rlp.decode(rc.encode()))
    assert back.logs == rc.logs


# -- precompiles ----------------------------------------------------------

def test_precompile_identity_and_sha256():
    s = st()
    e = EVM(s, BlockCtx())
    res = e.call(A, (4).to_bytes(20, "big"), 0, b"hello", 10_000)
    assert res.success and res.output == b"hello"
    import hashlib
    res = e.call(A, (2).to_bytes(20, "big"), 0, b"hello", 10_000)
    assert res.success and res.output == hashlib.sha256(b"hello").digest()


def test_precompile_ecrecover_matches_host():
    from eges_tpu.crypto import secp256k1 as host

    priv = bytes(range(1, 33))
    msg = keccak256(b"evm precompile")
    sig = host.ecdsa_sign(msg, priv)
    want = host.pubkey_to_address(host.privkey_to_pubkey(priv))
    data = (msg + (27 + sig[64]).to_bytes(32, "big") + sig[:32] + sig[32:64])
    s = st()
    e = EVM(s, BlockCtx())
    res = e.call(A, (1).to_bytes(20, "big"), 0, data, 10_000)
    assert res.success
    assert res.output == bytes(12) + want
    # corrupted sig -> empty output, still success (mainnet semantics)
    bad = bytearray(data); bad[80] ^= 0xFF
    res = e.call(A, (1).to_bytes(20, "big"), 0, bytes(bad), 10_000)
    assert res.success and (res.output == b"" or res.output[12:] != want)


def test_calls_between_contracts_and_staticcall():
    s = st()
    # callee: returns CALLVALUE; SSTORE(1,1) would violate static
    callee = bytes.fromhex("34600052602060 00f3".replace(" ", ""))
    s.set_code(B, callee)
    # caller: CALL B with value 5; forward returndata
    # PUSH1 0 (retlen) PUSH1 0 (retoff) PUSH1 0 (arglen) PUSH1 0 (argoff)
    # PUSH1 5 (value) PUSH20 B PUSH3 gas CALL
    caller_addr = b"\xdd" * 20
    code = (bytes.fromhex("6000600060006000 6005 73".replace(" ", "")) + B
            + bytes.fromhex("62030d40 f1 3d6000 3e 3d6000f3".replace(" ", "")))
    # ^ CALL; RETURNDATASIZE PUSH1 0 ... copy to mem and return it
    code = (bytes.fromhex("60006000600060006005 73".replace(" ", "")) + B
            + bytes.fromhex("62030d40f1503d600060003e3d60 00f3".replace(" ", "")))
    s.set_code(caller_addr, code)
    s.add_balance(caller_addr, 10)
    e = EVM(s, BlockCtx())
    res = e.call(A, caller_addr, 0, b"", 1_000_000)
    assert res.success
    assert int.from_bytes(res.output, "big") == 5
    assert s.balance(B) == 5


def test_intrinsic_gas_and_calldata_pricing():
    assert intrinsic_gas(b"", False) == G_TX
    assert intrinsic_gas(b"\x00\x01", False) == G_TX + 4 + 68


def test_process_block_roots_evm_effects():
    """EVM execution flows into state/receipt roots via process_block."""
    from eges_tpu.core.types import Header, new_block
    from eges_tpu.core.state import receipts_root

    s = StateDB.from_alloc({A: 10 * ETH})
    runtime = bytes.fromhex("600054600101806000556000526020 6000f3".replace(" ", ""))
    n = len(runtime)
    init = bytes([0x60, n, 0x60, 0x0C, 0x60, 0x00, 0x39,
                  0x60, n, 0x60, 0x00, 0xF3]) + runtime
    txn = Transaction(nonce=0, gas_price=1, gas_limit=500_000, to=None,
                      payload=init)
    blk = new_block(Header(number=1, coinbase=COINBASE), txs=[txn])
    state, receipts, gas = process_block(s, blk, [A])
    assert receipts[0].status == 1
    assert gas == receipts[0].cumulative_gas_used
    caddr = contract_address(A, 0)
    assert state.code(caddr) == runtime
    assert state.root() != s.root()
    assert receipts_root(receipts) != receipts_root(())


def test_bn256_precompiles():
    """EIP-196/197 precompiles 0x06-0x08 (ref: core/vm/contracts.go
    bn256Add/ScalarMul/Pairing over crypto/bn256)."""
    from eges_tpu.crypto import bn254 as bn

    s = st()
    e = EVM(s, BlockCtx())

    def enc_g1(pt):
        if pt is None:
            return bytes(64)
        return pt[0].to_bytes(32, "big") + pt[1].to_bytes(32, "big")

    def enc_g2(pt):
        (xr, xi), (yr, yi) = pt
        return b"".join(v.to_bytes(32, "big") for v in (xi, xr, yi, yr))

    # ECADD: G1 + G1 == 2*G1
    res = e.call(A, (6).to_bytes(20, "big"), 0,
                 enc_g1(bn.G1) + enc_g1(bn.G1), 10_000)
    assert res.success
    assert res.output == enc_g1(bn.g1_mul(2, bn.G1))
    # ECMUL: 7 * G1
    res = e.call(A, (7).to_bytes(20, "big"), 0,
                 enc_g1(bn.G1) + (7).to_bytes(32, "big"), 100_000)
    assert res.success and res.output == enc_g1(bn.g1_mul(7, bn.G1))
    # ECPAIRING: e(P,Q) * e(-P,Q) == 1 -> returns 1
    neg_g1 = (bn.G1[0], (-bn.G1[1]) % bn.P)
    data = (enc_g1(bn.G1) + enc_g2(bn.G2)
            + enc_g1(neg_g1) + enc_g2(bn.G2))
    res = e.call(A, (8).to_bytes(20, "big"), 0, data, 2_000_000)
    assert res.success and int.from_bytes(res.output, "big") == 1
    # an unbalanced pairing returns 0
    res = e.call(A, (8).to_bytes(20, "big"), 0,
                 enc_g1(bn.G1) + enc_g2(bn.G2), 2_000_000)
    assert res.success and int.from_bytes(res.output, "big") == 0
    # invalid point consumes the frame's gas (error semantics)
    bad = (123).to_bytes(32, "big") + (45).to_bytes(32, "big") + bytes(64)
    res = e.call(A, (6).to_bytes(20, "big"), 0, bad, 10_000)
    assert not res.success


def test_modexp_precompile():
    """0x05 bigModExp (EIP-198; ref: core/vm/contracts.go bigModExp)."""
    s = st()
    e = EVM(s, BlockCtx())

    def enc(base: int, exp: int, mod: int, bl=32, el=32, ml=32):
        return (bl.to_bytes(32, "big") + el.to_bytes(32, "big")
                + ml.to_bytes(32, "big") + base.to_bytes(bl, "big")
                + exp.to_bytes(el, "big") + mod.to_bytes(ml, "big"))

    res = e.call(A, (5).to_bytes(20, "big"), 0, enc(3, 200, 1000), 100_000)
    assert res.success
    assert int.from_bytes(res.output, "big") == pow(3, 200, 1000)
    # zero modulus -> zero output; empty mod length -> empty output
    res = e.call(A, (5).to_bytes(20, "big"), 0, enc(3, 5, 0), 100_000)
    assert res.success and int.from_bytes(res.output, "big") == 0
    res = e.call(A, (5).to_bytes(20, "big"), 0, enc(3, 5, 0, ml=0),
                 100_000)
    assert res.success and res.output == b""
    # gas too small for a big exponent fails the frame
    res = e.call(A, (5).to_bytes(20, "big"), 0,
                 enc((1 << 255) | 1, (1 << 255) | 1, (1 << 255) | 1), 300)
    assert not res.success


def test_delegatecall_keeps_caller_and_storage_context():
    """DELEGATECALL runs the library's code in the caller's storage with
    the ORIGINAL caller visible (ref: evm.DelegateCall semantics)."""
    s = st()
    lib = b"\xb1" * 20  # library address
    proxy = b"\xd2" * 20
    # library runtime: SSTORE(0, CALLER); store 7 at slot1
    lib_code = bytes.fromhex("33600055600760015500")
    s.set_code(lib, lib_code)
    # proxy runtime: DELEGATECALL(gas, lib, 0,0,0,0); STOP
    proxy_code = (bytes.fromhex("600060006000600073") + lib
                  + bytes.fromhex("62030d40f45000"))
    s.set_code(proxy, proxy_code)
    e = EVM(s, BlockCtx())
    res = e.call(A, proxy, 0, b"", 500_000)
    assert res.success
    # storage wrote to the PROXY, not the library
    assert s.storage_at(proxy, 1) == 7
    assert s.storage_at(lib, 1) == 0
    # CALLER inside the delegated frame is the proxy's caller (A)
    assert s.storage_at(proxy, 0) == int.from_bytes(A, "big")


def test_blockhash_serves_only_previous_256_ancestors():
    """Distance 0 (the block being executed — hash not yet sealed) and
    distances > 256 push zero; 1..256 hit the callable (round-3 advisor;
    ref core/vm/instructions.go opBlockhash)."""
    served = []

    def bh(n):
        served.append(n)
        return n.to_bytes(32, "big")

    # PUSH1 <n>, BLOCKHASH, PUSH1 0, MSTORE, PUSH1 32, PUSH1 0, RETURN
    def probe(n):
        code = bytes([0x60, n, 0x40, 0x60, 0x00, 0x52,
                      0x60, 0x20, 0x60, 0x00, 0xF3])
        s = st()
        s.set_code(B, code)
        e = EVM(s, BlockCtx(coinbase=COINBASE, number=7, time=99,
                            blockhash=bh))
        res = e.call(A, B, 0, b"", 1_000_000)
        assert res.success
        return int.from_bytes(res.output, "big")

    assert probe(6) == 6          # distance 1: served
    assert probe(7) == 0          # distance 0: the current block — zero
    assert probe(8) == 0          # future block — zero
    assert 7 not in served and 8 not in served
