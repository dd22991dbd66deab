"""Blocks of contract calls against the benchmark's plain reference: what
``c1024sb.calls-backlog`` holds the program to on the chip, at a size a
test can hold (6 blocks of 48 Smallbank calls over 300 customers), on both
rungs of the persistent trie.

The seeded chain of ``perfbench/gen_contracts.py`` carries the REFERENCE's
commitments in every header: the calls run under ``perfbench/ref/evm.py``
(upstream v1.8.2's gas table), the contract's storage trie and the
accounts' trie under ``perfbench/ref/trie.py``.  The program takes the
genesis with the contract's code and storage, decodes each validate request
off the wire, executes the block through ``apply_txn`` -> ``EVM.call`` and
must arrive at the same state root, storage root, receipts root and gas of
every receipt, status 0 for the calls that abort, and a storage trie that
no longer holds a slot written 0.
"""

import pytest

from eges_tpu.consensus import messages as M
from eges_tpu.core import evm as evm_mod
from eges_tpu.core.chain import (BlockChain, ChainError, MemoryStore,
                                 make_genesis)
from eges_tpu.core.evm import EVM, BlockCtx
from eges_tpu.core.state import (BLOCK_GAS_LIMIT, StateDB, StateError,
                                 apply_txn, block_ctx, process_block,
                                 receipts_root, recover_senders)
from eges_tpu.core.types import Header, Transaction
from eges_tpu.crypto.scheduler import VerifierScheduler
from eges_tpu.crypto.verify_host import NativeBatchVerifier
from eges_tpu.utils import tracing
from eges_tpu.utils.metrics import DEFAULT as metrics
from perfbench import gen_contracts
from perfbench.ref import contracts
from perfbench.ref import evm as ref_evm
from perfbench.ref import state as ref_state
from perfbench.ref import trie as ref_trie
from perfbench.ref.keccak import keccak256, keccak256_many
from tests.test_trie_native import trie_rung  # noqa: F401 (a fixture)

DEPLOY = {"validators": 16, "committee": 4, "acceptors": 16,
          "txn_per_block": 48, "gossip_window": 16, "duplicate_share": 0.25,
          "unseen_share": 0.10, "invalid_every": 8, "accounts": 96,
          "senders": 24, "balance_wei": 10**18, "bad_block_every": 2,
          "chain_blocks": 6, "customers": 300, "hot_customers": 10,
          "hot_share": 0.25,
          "mix": {"almagate": 15, "getBalance": 15, "updateBalance": 15,
                  "sendPayment": 25, "updateSaving": 15, "writeCheck": 15},
          "amounts": {"updateBalance": 1, "updateSaving": 20,
                      "sendPayment": 5, "writeCheck": 5},
          "balance_min": 10000, "balance_max": 50000, "abort_every": 8,
          "call_gas_limit": 100000, "block_gas_limit": 2**31,
          "contract_address": "5b" * 20}
COUNTERS = ("evm.calls", "evm.reverts", "evm.ops", "evm.sloads",
            "evm.sstores", "evm.slot_deletes", "evm.gas_used",
            "evm.gas_refunded")
_FEEDS: dict = {}


def feed_of(seed: int, first_bad: str = "state_root"):
    """A seed's chain, made once a process (the reference's Keccak costs a
    few milliseconds a call at this size)."""
    key = (seed, first_bad)
    if key not in _FEEDS:
        _FEEDS[key] = gen_contracts.ContractFeed(seed, DEPLOY,
                                                 first_bad=first_bad)
    return _FEEDS[key]


def chain_of(feed, sched, store=None) -> BlockChain:
    chain = BlockChain(store=store, verifier=sched, alloc=feed.alloc(),
                       gas_limit=feed.gas_limit)
    # the genesis it made is the one make_genesis makes
    assert chain.genesis.hash == make_genesis(
        alloc=feed.alloc(), gas_limit=feed.gas_limit).hash
    return chain


def counters() -> dict:
    return {n: metrics.counter(n).value for n in COUNTERS}


def play(feed) -> list:
    """The chain through the program, a height at a time: every sound
    block's pieces against the header the reference wrote."""
    sched = VerifierScheduler(NativeBatchVerifier(), max_batch=16)
    out, per_blk = [], DEPLOY["txn_per_block"]
    try:
        chain = chain_of(feed, sched)
        assert chain.genesis.hash == feed.genesis_hash
        assert chain.genesis.header.gas_limit == 2**31
        for p, steps in enumerate(feed.steps):
            for step in steps:
                code, msg = M.unpack_gossip(step.data)
                if step.what == "confirm":
                    if step.sound:
                        assert chain.offer(block.with_confirm(msg))
                    continue
                block = msg.block
                assert block.hash == step.block_hash
                took = chain.validate_candidate(block)
                out.append((p, step.bad, took))
                if not step.sound:
                    continue
                hdr, parent = feed.headers[p], chain.head_state()
                senders = recover_senders(block.transactions, sched)
                before = counters()
                state, receipts, gas = process_block(parent, block, senders)
                grew = {n: v - before[n] for n, v in counters().items()}
                statuses, cumulative = feed.receipts[p]
                assert [r.status for r in receipts] == statuses
                assert [r.cumulative_gas_used for r in receipts] \
                    == cumulative
                assert [not s for s in statuses] \
                    == feed.aborted[p * per_blk:(p + 1) * per_blk]
                assert gas == hdr["gas_used"] == cumulative[-1]
                assert receipts_root(receipts) == hdr["receipt_hash"]
                assert state.root() == hdr["root"] == feed.state_roots[p + 1]
                contract = state.account(feed.contract)
                assert contract.storage_root() == feed.storage_roots[p + 1]
                assert contract.code_hash == gen_contracts.CODE_HASH
                # a slot written 0 is gone from the trie, not a leaf of 0
                held = dict(contract.storage.items())
                for (m, c), value in feed.slot_deltas[p].items():
                    key = keccak256(feed.slot[m, c].to_bytes(32, "big"))
                    assert (key in held) == bool(value)
                    assert state.storage_at(feed.contract,
                                            feed.slot[m, c]) == value
                # the new counters, a block at a time
                assert grew["evm.calls"] == per_blk
                assert grew["evm.reverts"] == statuses.count(0)
                assert grew["evm.gas_used"] == cumulative[-1]
                assert grew["evm.slot_deletes"] >= sum(
                    1 for v in feed.slot_deltas[p].values() if not v)
                assert grew["evm.gas_refunded"] > 0
                assert grew["evm.sstores"] >= len(feed.slot_deltas[p])
                assert grew["evm.ops"] > 40 * per_blk
            assert chain.height() == p + 1
            assert chain.head().hash == feed.block_hashes[p]
        want = feed.state_at(len(feed.steps))
        head = chain.head_state()
        assert {a: (head.nonce(a), head.balance(a)) for a in want} == want
    finally:
        sched.close()
    return out


@pytest.mark.parametrize("seed", [5, 2**31 + 7])
def test_the_program_runs_smallbank_as_the_plain_reference(seed, trie_rung):
    feed = feed_of(seed)
    got = play(feed)
    assert all(took for _p, bad, took in got if bad is None)
    assert sum(1 for _p, bad, _t in got if bad is None) == 6
    # some balance became 0 and left the trie, some call aborted
    assert any(not v for d in feed.slot_deltas for v in d.values())
    assert sum(feed.aborted) >= 6 * 48 // 8


@pytest.mark.parametrize("kind", gen_contracts.BAD_KINDS)
def test_each_kind_of_bad_block_of_calls_is_refused(kind):
    feed = feed_of(2**31 + 13, first_bad=kind)
    got = play(feed)
    mine = [took for _p, bad, took in got if bad == kind]
    # a certificate's block is sound (its confirm is what is not)
    assert mine and all(took == (kind == "certificate") for took in mine)


def test_a_span_carries_the_calls_and_the_storage_root_has_its_own(trie_rung):
    feed = feed_of(5)
    sched = VerifierScheduler(NativeBatchVerifier(), max_batch=16)
    try:
        chain = chain_of(feed, sched)
        _code0, msg0 = M.unpack_gossip(feed.steps[0][1].data)
        assert chain.validate_candidate(msg0.block)
        spans = {s["name"]: s for s in tracing.DEFAULT.finished(64)}
    finally:
        sched.close()
    assert {"chain.execute", "state.storage_root"} <= set(tracing.SPANS)
    ex = spans["chain.execute"]["attrs"]
    assert ex["evm_calls"] == 48 and ex["reverted"] == \
        feed.receipts[0][0].count(0)
    st = spans["state.storage_root"]["attrs"]
    assert st["accounts"] == 1 and st["slots"] >= len(feed.slot_deltas[0])


# -- each opcode's gas against the reference's table ------------------------

def _operands(op: int, name: str, pops: int) -> list:
    """Operands that keep the opcode on its plain path: small offsets, a
    jump to the JUMPDEST the case's code ends with."""
    if name in ("JUMP", "JUMPI"):
        return [None] + [1] * (pops - 1)     # None: the JUMPDEST's offset
    return [3] * pops


def _case_code(op: int) -> bytes:
    name, pops, _pushes, _gas = ref_evm.OPS[op]
    if name.startswith("PUSH"):
        return bytes([op]) + bytes(range(1, op - 0x5F + 1))
    args = _operands(op, name, pops)
    body = b""
    for a in reversed(args):             # the first operand ends on top
        body += bytes([0x60, 0 if a is None else a])
    body += bytes([op])
    if name in ("JUMP", "JUMPI"):
        dest = len(body)
        body = body.replace(bytes([0x60, 0]), bytes([0x60, dest]), 1) + b"\x5b"
    return body


@pytest.mark.parametrize("op", sorted(ref_evm.OPS),
                         ids=lambda op: ref_evm.OPS[op][0])
def test_an_opcode_costs_what_upstream_charges(op):
    """One opcode after the PUSH1s of its operands, run by the program's
    interpreter and by the reference's: the same gas, the same end."""
    code = _case_code(op)
    addr = bytes.fromhex("c0de" * 10)
    state = StateDB()
    state.set_code(addr, code)
    state.set_storage_many(addr, {3: 9})   # SLOAD / SSTORE meet a value
    res = EVM(state, BlockCtx()).call(bytes(20), addr, 0, b"\x01" * 40,
                                      100_000)
    want = ref_evm.run(code, b"\x01" * 40, {3: 9}, 100_000, keccak256)
    assert res.success == bool(want.status)
    assert res.gas_used == 100_000 - want.gas_left, ref_evm.OPS[op][0]


def test_sstore_gas_and_refund_by_case():
    """0 -> v sets, v -> 0 clears and earns the refund, v -> w resets;
    the refund is capped at half of what the transaction used."""
    addr, sender = bytes.fromhex("c0de" * 10), bytes.fromhex("11" * 20)
    # SSTORE(slot calldata[0], value calldata[32])
    code = bytes([0x60, 32, 0x35, 0x60, 0, 0x35, 0x55, 0x00])
    for slot, value in ((1, 7), (2, 0), (2, 5), (1, 0)):
        state = StateDB.from_alloc({sender: 1, addr: {
            "code": code, "storage": {2: 4}}})
        data = slot.to_bytes(32, "big") + value.to_bytes(32, "big")
        r = apply_txn(state, Transaction(
            nonce=0, gas_price=0, gas_limit=90_000, to=addr, value=0,
            payload=data), sender, bytes(20), 0)
        status, used, writes = ref_evm.apply_call(code, data, {2: 4}, 90_000,
                                                  keccak256)
        assert (r.status, r.cumulative_gas_used) == (status, used)
        assert state.storage_at(addr, slot) == value == writes[slot]


# -- the genesis -------------------------------------------------------------

def test_a_genesis_with_code_and_storage_has_the_references_root(trie_rung):
    feed = feed_of(5)
    alloc = feed.alloc()
    state = StateDB.from_alloc(alloc)
    assert state.root() == feed.state_roots[0]
    acct = state.account(feed.contract)
    assert (acct.nonce, acct.balance) == (1, 0)
    assert acct.storage_root() == feed.storage_roots[0]
    assert state.code(feed.contract) == contracts.SMALLBANK
    # the storage root as the plain trie gives it, a key at a time
    slots = sorted(feed.genesis_storage)
    keys = keccak256_many(s.to_bytes(32, "big") for s in slots)
    from perfbench.ref import rlp
    assert ref_trie.root_of(
        (k, rlp.encode(feed.genesis_storage[s]))
        for k, s in zip(keys, slots)) == feed.storage_roots[0]
    # a balance alone stays valid, and means what it meant
    plain = {a: feed.balance for a in feed.addrs}
    assert StateDB.from_alloc(plain).root() == ref_state.state_root(
        {a: (0, feed.balance) for a in feed.addrs},
        dict(zip(feed.addrs, keccak256_many(feed.addrs))))
    with pytest.raises(ValueError):
        StateDB.from_alloc({feed.contract: {"codes": b"\x00"}})


def test_a_chain_reopened_on_its_store_keeps_the_contract(trie_rung):
    feed = feed_of(5)
    sched = VerifierScheduler(NativeBatchVerifier(), max_batch=16)
    store = MemoryStore()
    try:
        chain = chain_of(feed, sched, store)
        for p in range(2):
            for step in feed.steps[p]:
                _code, msg = M.unpack_gossip(step.data)
                if step.what == "request" and step.sound:
                    block = msg.block
                elif step.what == "confirm" and step.sound:
                    assert chain.offer(block.with_confirm(msg))
        assert chain.height() == 2
        again = BlockChain(store=store, verifier=sched, alloc=feed.alloc())
        assert again.genesis.hash == feed.genesis_hash
        assert again.height() == 2
        assert again.head_state().root() == feed.state_roots[2]
        assert again.head_state().account(feed.contract).storage_root() \
            == feed.storage_roots[2]
        # an allocation that lacks the contract is not this chain's
        with pytest.raises(ChainError):
            BlockChain(store=store, verifier=sched,
                       alloc={a: feed.balance for a in feed.addrs})
    finally:
        sched.close()


# -- the block gas limit is the header's ---------------------------------------

def test_the_block_gas_limit_is_the_headers():
    addr, sender = bytes.fromhex("c0de" * 10), bytes.fromhex("11" * 20)
    state = StateDB.from_alloc({sender: 1, addr: {"code": b"\x00"}})
    tx = Transaction(nonce=0, gas_price=0, gas_limit=60_000, to=addr,
                     value=0, payload=b"")
    # a header that says 0 reads as the one constant
    assert block_ctx(Header()).gas_limit == BLOCK_GAS_LIMIT \
        == BlockCtx().gas_limit == 30_000_000
    small = block_ctx(Header(gas_limit=100_000))
    assert small.gas_limit == 100_000
    r = apply_txn(state.copy(), tx, sender, bytes(20), 30_000, ctx=small)
    assert r.status == 1
    with pytest.raises(StateError, match="block gas limit"):
        apply_txn(state.copy(), tx, sender, bytes(20), 50_000, ctx=small)
    # above the old constant under a header that allows it
    big = block_ctx(Header(gas_limit=2**31))
    assert apply_txn(state.copy(), tx, sender, bytes(20), 40_000_000,
                     ctx=big).status == 1
    with pytest.raises(StateError, match="block gas limit"):
        apply_txn(state.copy(), tx, sender, bytes(20), 40_000_000)


@pytest.mark.parametrize("gas_limit", [0, 2**31])
def test_a_block_built_on_a_chain_carries_its_parents_gas_limit(gas_limit):
    from eges_tpu.core.engine import DevEngine
    from eges_tpu.crypto import secp256k1 as secp

    priv = (7).to_bytes(32, "big")
    engine = DevEngine(
        secp.pubkey_to_address(secp.privkey_to_pubkey(priv)), priv)
    chain = BlockChain(genesis=make_genesis(gas_limit=gas_limit),
                       engine=engine)
    blk = engine.seal_next(chain)
    assert blk.header.gas_limit == gas_limit
    assert chain.head().header.gas_limit == gas_limit
    assert chain.make_empty_block().header.gas_limit == gas_limit
    # a genesis that says nothing keeps today's hash
    assert make_genesis().hash == make_genesis(gas_limit=0).hash
    assert (make_genesis(gas_limit=5).hash == make_genesis().hash) is False


def test_the_tally_is_added_once_a_block_and_reads_zero_after():
    tally = evm_mod.Tally(calls=3, reverts=1, ops=90, gas_used=70_000)
    before = counters()
    tally.flush()
    grew = {n: v - before[n] for n, v in counters().items()}
    assert (grew["evm.calls"], grew["evm.reverts"], grew["evm.ops"],
            grew["evm.gas_used"]) == (3, 1, 90, 70_000)
    assert tally == evm_mod.Tally()
    tally.flush()  # nothing to add, nothing added
    assert counters() == {n: before[n] + grew[n] for n in before}
