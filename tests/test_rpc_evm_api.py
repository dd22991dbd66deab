"""RPC EVM-surface tests: eth_call, estimateGas, getLogs, filters,
gasPrice, getCode/getStorageAt, debug_* namespace (ref roles:
internal/ethapi/api.go Call, eth/filters/, eth/gasprice/,
internal/debug/api.go)."""

import pytest

from eges_tpu.core.chain import BlockChain, make_genesis
from eges_tpu.core.state import contract_address
from eges_tpu.core.types import Header, Transaction, new_block
from eges_tpu.crypto import secp256k1 as secp
from eges_tpu.rpc.server import RpcError, RpcServer

PRIV = bytes([7]) * 32
ADDR = secp.pubkey_to_address(secp.privkey_to_pubkey(PRIV))
ETH = 10**18

# runtime: counter at slot0 with a LOG1(topic=7) on each call
# SLOAD(0) 1 ADD DUP1 SSTORE(0) MSTORE(0); LOG1(0,32,topic 7); RETURN 32
RUNTIME = bytes.fromhex(
    "600054600101806000556000526007602060" + "00a1" + "602060" + "00f3")
INIT = (bytes([0x60, len(RUNTIME), 0x60, 0x0C, 0x60, 0x00, 0x39,
               0x60, len(RUNTIME), 0x60, 0x00, 0xF3]) + RUNTIME)


def _signed(nonce, to, payload=b"", gas=500_000, price=2):
    t = Transaction(nonce=nonce, gas_price=price, gas_limit=gas, to=to,
                    value=0, payload=payload)
    return t.signed(PRIV)


def _chain_with_contract():
    chain = BlockChain(genesis=make_genesis(alloc={ADDR: 10 * ETH}),
                       alloc={ADDR: 10 * ETH})
    caddr = contract_address(ADDR, 0)
    txs = [_signed(0, None, INIT), _signed(1, caddr), _signed(2, caddr)]
    kept, root, rroot, gas, bloom = chain.execute_preview(txs, coinbase=bytes(20))
    assert len(kept) == 3
    head = chain.head()
    blk = new_block(Header(parent_hash=head.hash, number=1,
                           time=head.header.time + 1, root=root,
                           receipt_hash=rroot, gas_used=gas,
                           bloom=bloom), txs=kept)
    assert chain.offer(blk), chain.last_error
    return chain, caddr


def test_eth_call_and_estimate_and_state_readers():
    chain, caddr = _chain_with_contract()
    rpc = RpcServer(chain)
    # two on-chain calls happened: slot0 == 2
    assert rpc.dispatch("eth_getStorageAt",
                        ["0x" + caddr.hex(), "0x0"]).endswith("02")
    assert rpc.dispatch("eth_getCode",
                        ["0x" + caddr.hex()]) == "0x" + RUNTIME.hex()
    # eth_call runs read-only: returns 3 without mutating the chain
    out = rpc.dispatch("eth_call", [{"from": "0x" + ADDR.hex(),
                                     "to": "0x" + caddr.hex()}])
    assert int(out, 16) == 3
    assert rpc.dispatch("eth_getStorageAt",
                        ["0x" + caddr.hex(), "0x0"]).endswith("02")
    gas = int(rpc.dispatch("eth_estimateGas",
                           [{"from": "0x" + ADDR.hex(),
                             "to": "0x" + caddr.hex()}]), 16)
    assert gas > 20_000


def test_get_logs_and_filters():
    chain, caddr = _chain_with_contract()
    rpc = RpcServer(chain)
    logs = rpc.dispatch("eth_getLogs", [{"fromBlock": "0x0",
                                         "toBlock": "0x1"}])
    assert len(logs) == 2  # one per contract call
    assert logs[0]["address"] == "0x" + caddr.hex()
    topic7 = "0x" + (7).to_bytes(32, "big").hex()
    assert logs[0]["topics"] == [topic7]
    # topic filtering
    assert rpc.dispatch("eth_getLogs", [{
        "fromBlock": "0x0", "topics": [topic7]}]) == logs
    assert rpc.dispatch("eth_getLogs", [{
        "fromBlock": "0x0",
        "topics": ["0x" + (8).to_bytes(32, "big").hex()]}]) == []
    # address filtering
    assert rpc.dispatch("eth_getLogs", [{
        "fromBlock": "0x0", "address": "0x" + bytes(20).hex()}]) == []
    # polling filters
    fid = rpc.dispatch("eth_newFilter", [{"topics": [topic7]}])
    assert rpc.dispatch("eth_getFilterChanges", [fid]) == []
    bfid = rpc.dispatch("eth_newBlockFilter", [{}])
    # a receipt lookup for a logging txn carries its logs
    blk = chain.get_block_by_number(1)
    rcpt = rpc.dispatch("eth_getTransactionReceipt",
                        ["0x" + blk.transactions[1].hash.hex()])
    assert rcpt["logs"] and rcpt["logs"][0]["topics"] == [topic7]
    assert rpc.dispatch("eth_uninstallFilter", [fid]) is True
    with pytest.raises(RpcError):
        rpc.dispatch("eth_getFilterChanges", [fid])
    assert rpc.dispatch("eth_uninstallFilter", [bfid]) is True


def test_gas_price_oracle_and_debug():
    chain, _ = _chain_with_contract()
    rpc = RpcServer(chain)
    assert int(rpc.dispatch("eth_gasPrice", []), 16) == 2  # median price
    # debug namespace
    assert rpc.dispatch("debug_startProfile", []) is True
    report = rpc.dispatch("debug_stopProfile", [5])
    assert "cumulative" in report or "function calls" in report
    stacks = rpc.dispatch("debug_stacks", [])
    assert "thread" in stacks
    stats = rpc.dispatch("debug_stats", [])
    assert stats["threads"] >= 1
    # the collector as the node left it (utils/heap.py): what is frozen,
    # and the thresholds nobody touches
    assert stats["gc_frozen"] >= 0
    assert stats["gc_thresholds"] == [700, 10, 10]


def test_get_transaction_by_hash_and_chain_id():
    chain, caddr = _chain_with_contract()
    rpc = RpcServer(chain)
    blk = chain.get_block_by_number(1)
    h = blk.transactions[1].hash
    got = rpc.dispatch("eth_getTransactionByHash", ["0x" + h.hex()])
    assert got["hash"] == "0x" + h.hex()
    assert got["blockNumber"] == "0x1"
    assert got["transactionIndex"] == "0x1"
    assert got["to"] == "0x" + caddr.hex()
    assert rpc.dispatch("eth_getTransactionByHash",
                        ["0x" + bytes(32).hex()]) is None
    assert int(rpc.dispatch("eth_chainId", []), 16) == 930412


def test_debug_trace_transaction_struct_logs():
    """VERDICT r3 #8 (ref eth/tracers/tracer.go role): replaying a mined
    txn yields geth-shaped struct logs; a reverting call traces as
    failed with the fault tagged on its last step."""
    chain, caddr = _chain_with_contract()
    rpc = RpcServer(chain)
    blk = chain.get_block_by_number(1)

    # txn 2 is the SECOND contract call: its pre-state must include txn
    # 1's increment, proving the preceding-txns replay
    trace = rpc.dispatch("debug_traceTransaction",
                         ["0x" + blk.transactions[2].hash.hex()])
    assert trace["failed"] is False and trace["gas"] > 21_000
    ops = [s["op"] for s in trace["structLogs"]]
    assert ops == ["PUSH1", "SLOAD", "PUSH1", "ADD", "DUP1", "PUSH1",
                   "SSTORE", "PUSH1", "MSTORE", "PUSH1", "PUSH1", "PUSH1",
                   "LOG1", "PUSH1", "PUSH1", "RETURN"]
    # SLOAD sees txn 1's write: stack top after SLOAD (step 2's stack
    # holds the loaded value at its top) == 1
    assert trace["structLogs"][2]["stack"][-1] == "0x1"
    assert all(s["depth"] == 1 for s in trace["structLogs"])
    # every non-terminal step settles positive; RETURN's base cost is a
    # legitimate 0 — but the costs must telescope to the frame's
    # execution gas exactly (txn gas minus the 21k intrinsic), which
    # only holds when the terminal step settled too (on_frame_end)
    assert all(s["gasCost"] > 0 for s in trace["structLogs"][:-1])
    assert sum(s["gasCost"] for s in trace["structLogs"]) \
        == trace["gas"] - 21_000

    # a frame-terminal opcode with REAL cost (RETURN that expands
    # memory) settles via on_frame_end, not as a leftover zero
    from eges_tpu.core.evm import EVM, BlockCtx
    from eges_tpu.core.state import Account, StateDB
    from eges_tpu.core.tracer import StructLogTracer
    st = StateDB({ADDR: Account(balance=ETH)})
    expander = b"\x42" * 20
    st.set_code(expander, bytes.fromhex("60206000f3"))  # RETURN(0, 32)
    tr = StructLogTracer()
    res = EVM(st, BlockCtx(coinbase=bytes(20)), tracer=tr).call(
        ADDR, expander, 0, b"", 100_000)
    assert res.success and len(res.output) == 32
    last = tr.result(gas_used=res.gas_used, failed=False,
                     output=res.output)["structLogs"][-1]
    assert last["op"] == "RETURN" and last["gasCost"] == 3  # 1-word grow

    # a failing call: deploy PUSH1 0 PUSH1 0 REVERT and call it
    revert_rt = bytes.fromhex("60006000fd")
    init = (bytes([0x60, len(revert_rt), 0x60, 0x0C, 0x60, 0x00, 0x39,
                   0x60, len(revert_rt), 0x60, 0x00, 0xF3]) + revert_rt)
    from eges_tpu.core.state import contract_address as _ca
    raddr = _ca(ADDR, 3)
    txs = [_signed(3, None, init), _signed(4, raddr)]
    kept, root, rroot, gas, bloom = chain.execute_preview(
        txs, coinbase=bytes(20))
    head = chain.head()
    blk2 = new_block(Header(parent_hash=head.hash, number=2,
                            time=head.header.time + 1, root=root,
                            receipt_hash=rroot, gas_used=gas,
                            bloom=bloom), txs=kept)
    assert chain.offer(blk2), chain.last_error
    trace = rpc.dispatch("debug_traceTransaction",
                         ["0x" + blk2.transactions[1].hash.hex()])
    assert trace["failed"] is True
    ops = [s["op"] for s in trace["structLogs"]]
    assert ops == ["PUSH1", "PUSH1", "REVERT"]
    assert trace["structLogs"][-1]["error"] == "execution reverted"

    # unknown hash is a clean RPC error
    with pytest.raises(RpcError):
        rpc.dispatch("debug_traceTransaction", ["0x" + "ab" * 32])
