"""State & execution layer (L3): account model, txn application,
state/receipt roots, and their enforcement on the insert + ACK paths
(ref: core/state_processor.go:93, core/state/statedb.go,
core/block_validator.go:82-105)."""

import dataclasses

import pytest

from eges_tpu.core.chain import BlockChain, ChainError, make_genesis
from eges_tpu.core.state import (
    Account, INTRINSIC_GAS, Receipt, StateDB, StateError, apply_txn,
    process_block, receipts_root, recover_senders,
)
from eges_tpu.core.trie import EMPTY_ROOT
from eges_tpu.core.txpool import TxPool
from eges_tpu.core.types import Header, Transaction, new_block
from eges_tpu.crypto import secp256k1 as secp
from eges_tpu.sim.cluster import SimCluster
from eges_tpu.sim.simnet import SimClock
from tests.test_trie_native import trie_rung  # noqa: F401 (a fixture)

# every case on both rungs of the persistent trie: the library's node
# store and the Python nodes (tests/test_trie_native.py old_library)
pytestmark = pytest.mark.usefixtures("trie_rung")

PRIV_A = bytes([0x11]) * 32
PRIV_B = bytes([0x22]) * 32
ADDR_A = secp.pubkey_to_address(secp.privkey_to_pubkey(PRIV_A))
ADDR_B = secp.pubkey_to_address(secp.privkey_to_pubkey(PRIV_B))
COINBASE = bytes([0xC0]) * 20
ETH = 10**18


def signed_txn(priv, nonce, to, value, gas_price=1):
    return Transaction(nonce=nonce, gas_price=gas_price,
                       gas_limit=INTRINSIC_GAS, to=to,
                       value=value).signed(priv, chain_id=1)


def test_state_root_and_accounts():
    s = StateDB()
    assert s.root() == EMPTY_ROOT
    s.add_balance(ADDR_A, 5 * ETH)
    r1 = s.root()
    assert r1 != EMPTY_ROOT
    s.add_balance(ADDR_B, ETH)
    assert s.root() != r1
    s.sub_balance(ADDR_B, ETH)
    assert s.root() == r1  # empty accounts pruned -> same root
    with pytest.raises(StateError):
        s.sub_balance(ADDR_B, 1)


def test_apply_txn_semantics():
    s = StateDB.from_alloc({ADDR_A: 2 * ETH})
    t = signed_txn(PRIV_A, 0, ADDR_B, ETH, gas_price=2)
    r = apply_txn(s, t, ADDR_A, COINBASE, 0)
    fee = 2 * INTRINSIC_GAS
    assert s.balance(ADDR_B) == ETH
    assert s.balance(ADDR_A) == ETH - fee
    assert s.balance(COINBASE) == fee
    assert s.nonce(ADDR_A) == 1
    assert r.cumulative_gas_used == INTRINSIC_GAS
    # nonce replay rejected
    with pytest.raises(StateError):
        apply_txn(s, t, ADDR_A, COINBASE, 0)
    # nonce gap rejected
    with pytest.raises(StateError):
        apply_txn(s, signed_txn(PRIV_A, 5, ADDR_B, 1), ADDR_A, COINBASE, 0)
    # insufficient balance rejected
    with pytest.raises(StateError):
        apply_txn(s, signed_txn(PRIV_A, 1, ADDR_B, 5 * ETH), ADDR_A,
                  COINBASE, 0)


def mk_chain(alloc):
    return BlockChain(genesis=make_genesis(alloc=alloc), alloc=alloc)


def block_with(chain, txs, coinbase=COINBASE):
    kept, root, rroot, gas, bloom = chain.execute_preview(list(txs), coinbase)
    parent = chain.head()
    return new_block(Header(parent_hash=parent.hash,
                            number=parent.number + 1, coinbase=coinbase,
                            time=parent.header.time + 1, root=root,
                            receipt_hash=rroot, gas_used=gas,
                            trust_rand=1),
                     txs=kept)


def test_chain_applies_transactions():
    chain = mk_chain({ADDR_A: 2 * ETH})
    t = signed_txn(PRIV_A, 0, ADDR_B, ETH)
    blk = block_with(chain, [t])
    assert chain.offer(blk)
    st = chain.head_state()
    assert st.balance(ADDR_B) == ETH
    assert st.nonce(ADDR_A) == 1
    assert len(chain.receipts_of(blk.hash)) == 1
    assert chain.head().header.gas_used == INTRINSIC_GAS


def test_bad_state_root_rejected():
    chain = mk_chain({ADDR_A: 2 * ETH})
    t = signed_txn(PRIV_A, 0, ADDR_B, ETH)
    good = block_with(chain, [t])
    bad = dataclasses.replace(
        good, header=dataclasses.replace(good.header, root=b"\xab" * 32))
    assert chain.offer(bad) == []
    assert chain.bad_blocks == 1 and "state root" in chain.last_error
    # receipt-root lie also rejected
    bad2 = dataclasses.replace(
        good, header=dataclasses.replace(good.header,
                                         receipt_hash=b"\xcd" * 32))
    assert chain.offer(bad2) == []
    assert "receipt root" in chain.last_error
    assert chain.offer(good)


def test_nonce_gap_block_rejected_by_acceptor_and_insert():
    """VERDICT item 5's done-criterion: a block with a nonce-gap txn is
    rejected — by the acceptor's pre-ACK validation and by insert."""
    chain = mk_chain({ADDR_A: 2 * ETH})
    gap = signed_txn(PRIV_A, 7, ADDR_B, 1)  # state nonce is 0
    parent = chain.head()
    blk = new_block(Header(parent_hash=parent.hash, number=1,
                           coinbase=COINBASE, time=1,
                           root=parent.header.root, trust_rand=1),
                    txs=(gap,))
    assert not chain.validate_candidate(blk)
    assert chain.offer(blk) == []
    assert "nonce mismatch" in chain.last_error


def test_overspend_block_rejected():
    chain = mk_chain({ADDR_A: ETH})
    over = signed_txn(PRIV_A, 0, ADDR_B, 2 * ETH)
    parent = chain.head()
    blk = new_block(Header(parent_hash=parent.hash, number=1,
                           coinbase=COINBASE, time=1,
                           root=parent.header.root, trust_rand=1),
                    txs=(over,))
    assert not chain.validate_candidate(blk)
    assert chain.offer(blk) == []


def test_restart_rebuilds_state(tmp_path):
    from eges_tpu.core.chain import FileStore

    alloc = {ADDR_A: 2 * ETH}
    g = make_genesis(alloc=alloc)
    chain = BlockChain(store=FileStore(str(tmp_path / "d")), genesis=g,
                       alloc=alloc)
    t0 = signed_txn(PRIV_A, 0, ADDR_B, ETH)
    chain.offer(block_with(chain, [t0]))
    t1 = signed_txn(PRIV_B, 0, ADDR_A, ETH // 2, gas_price=0)
    chain.offer(block_with(chain, [t1]))
    assert chain.height() == 2
    chain.store.close()

    chain2 = BlockChain(store=FileStore(str(tmp_path / "d")), genesis=g,
                        alloc=alloc)
    assert chain2.height() == 2
    assert chain2.head_state().balance(ADDR_B) == ETH - ETH // 2
    assert chain2.head_state().nonce(ADDR_A) == 1
    assert len(chain2.receipts_of(chain2.head().hash)) == 1


def test_txpool_nonce_order_and_price_bump():
    clock = SimClock()
    pool = TxPool(clock, window_ms=0.0)
    t1 = signed_txn(PRIV_A, 1, ADDR_B, 1, gas_price=5)
    t0 = signed_txn(PRIV_A, 0, ADDR_B, 1, gas_price=5)
    pool.add_remotes([t1, t0])  # out of nonce order
    clock.run_until(clock.now() + 1)
    got = pool.pending_txns()
    assert [t.nonce for t in got] == [0, 1]
    # same-nonce replacement requires a >=10% higher gas price
    cheap = signed_txn(PRIV_A, 0, ADDR_B, 2, gas_price=5)
    pool.add_remotes([cheap])
    clock.run_until(clock.now() + 1)
    assert pool.pending[ADDR_A][0].hash == t0.hash
    rich = signed_txn(PRIV_A, 0, ADDR_B, 2, gas_price=6)
    pool.add_remotes([rich])
    clock.run_until(clock.now() + 1)
    assert pool.pending[ADDR_A][0].hash == rich.hash
    assert len(pool.pending_txns()) == 2


def test_pool_gap_sender_does_not_starve_others():
    """Review regression: a sender whose txns start beyond its state
    nonce (or exceed its balance) must not occupy the per-block limit;
    stale nonces are evicted."""
    clock = SimClock()
    pool = TxPool(clock, window_ms=0.0)
    # A: nonce gap (state nonce 0, txns start at 1); B: executable
    a_txns = [signed_txn(PRIV_A, n, ADDR_B, 1, gas_price=0)
              for n in (1, 2, 3, 4)]
    b_txn = signed_txn(PRIV_B, 0, ADDR_A, 1, gas_price=0)
    pool.add_remotes(a_txns + [b_txn])
    clock.run_until(clock.now() + 1)
    state = StateDB.from_alloc({ADDR_A: ETH, ADDR_B: ETH})
    got = pool.pending_txns(4, state=state)
    assert [t.hash for t in got] == [b_txn.hash]
    # an over-balance sender is equally skipped
    rich_spend = signed_txn(PRIV_B, 1, ADDR_A, 5 * ETH, gas_price=0)
    pool.add_remotes([rich_spend])
    clock.run_until(clock.now() + 1)
    got = pool.pending_txns(4, state=state)
    assert rich_spend.hash not in {t.hash for t in got}
    # stale (already-mined) nonces are evicted on selection
    state2 = StateDB.from_alloc({ADDR_A: ETH})
    state2.set_account(ADDR_A, Account(nonce=3, balance=ETH))
    got = pool.pending_txns(8, state=state2)
    assert {t.nonce for t in got if t.hash in {x.hash for x in a_txns}} == {3, 4}
    assert 1 not in pool.pending.get(ADDR_A, {})


def test_cluster_executes_signed_txns_end_to_end():
    """A signed txn submitted to one node's pool is included by whichever
    proposer drains it and executes on every node's state."""
    alloc = {ADDR_A: 2 * ETH}
    c = SimCluster(3, txn_per_block=2, seed=4, alloc=alloc, txpool=True)
    c.start()
    t = signed_txn(PRIV_A, 0, ADDR_B, ETH)
    for sn in c.nodes:  # no tx gossip yet: seed every pool
        sn.node.txpool.add_remotes([t])
    c.run(60, stop_condition=lambda: all(
        sn.chain.head_state().balance(ADDR_B) == ETH for sn in c.nodes))
    for sn in c.nodes:
        assert sn.chain.head_state().balance(ADDR_B) == ETH
        assert sn.chain.head_state().nonce(ADDR_A) == 1


def test_receipts_survive_pruning_and_restart(tmp_path):
    """Durable receipts/tx-index sidecar (ref: core/database_util.go
    WriteReceipts + WriteTxLookupEntries): lookups work beyond the
    in-memory state window and across restarts."""
    from eges_tpu.core.chain import FileStore

    alloc = {ADDR_A: 100 * ETH}
    store = FileStore(str(tmp_path / "chaindata"))
    chain = BlockChain(store=store, genesis=make_genesis(alloc=alloc),
                       alloc=alloc)
    keep = chain._STATE_KEEP
    chain._STATE_KEEP = 8  # shrink the window so pruning bites fast
    try:
        first_tx = None
        for n in range(1, 101):
            t = signed_txn(PRIV_A, n - 1, ADDR_B, 1, gas_price=0)
            if first_tx is None:
                first_tx = t
            blk = block_with(chain, [t])
            assert chain.offer(blk), chain.last_error
        # block 1 is far outside the 8-block window now
        assert chain.state_at(chain.get_block_by_number(1).hash) is None
        hit = chain.lookup_txn(first_tx.hash)
        assert hit is not None
        blk, i, rcpt = hit
        assert blk.number == 1 and rcpt is not None and rcpt.status == 1
    finally:
        chain._STATE_KEEP = keep
    store.close()

    # restart: the sidecar replays; history still answerable
    store2 = FileStore(str(tmp_path / "chaindata"))
    chain2 = BlockChain(store=store2, genesis=make_genesis(alloc=alloc),
                        alloc=alloc)
    hit = chain2.lookup_txn(first_tx.hash)
    assert hit is not None and hit[0].number == 1
    assert hit[2] is not None and hit[2].status == 1
    store2.close()


def test_receipts_log_torn_tail_truncates(tmp_path):
    """A torn receipts.log record is truncated on replay (not appended
    after forever) and the lost tail rebuilds as blocks re-insert."""
    import os

    from eges_tpu.core.chain import FileStore

    alloc = {ADDR_A: 100 * ETH}
    store = FileStore(str(tmp_path / "cd"))
    chain = BlockChain(store=store, genesis=make_genesis(alloc=alloc),
                       alloc=alloc)
    txs = []
    for n in range(1, 6):
        t = signed_txn(PRIV_A, n - 1, ADDR_B, 1, gas_price=0)
        txs.append(t)
        assert chain.offer(block_with(chain, [t])), chain.last_error
    store.close()

    rpath = str(tmp_path / "cd" / "receipts.log")
    size = os.path.getsize(rpath)
    with open(rpath, "r+b") as f:
        f.truncate(size - 7)  # tear mid-record

    sizes = []
    for _ in range(3):
        s2 = FileStore(str(tmp_path / "cd"))
        c2 = BlockChain(store=s2, genesis=make_genesis(alloc=alloc),
                        alloc=alloc)
        # replay re-derives receipts for every block, restoring lookups
        hit = c2.lookup_txn(txs[-1].hash)
        assert hit is not None and hit[2] is not None
        s2.close()
        sizes.append(os.path.getsize(rpath))
    # the log must not grow on every restart (the pre-fix behavior)
    assert sizes[1] == sizes[2], sizes


def test_blocks_log_torn_tail_truncates_and_resumes(tmp_path):
    """A crash mid-append leaves a torn blocks.log record: restart must
    truncate it and resume from the last good block (ref: the LevelDB
    atomicity the FileStore's fsync'd append-log replaces)."""
    import os

    from eges_tpu.core.chain import FileStore

    alloc = {ADDR_A: 10 * ETH}
    store = FileStore(str(tmp_path / "cd"))
    chain = BlockChain(store=store, genesis=make_genesis(alloc=alloc),
                       alloc=alloc)
    for n in range(1, 5):
        t = signed_txn(PRIV_A, n - 1, ADDR_B, 1, gas_price=0)
        assert chain.offer(block_with(chain, [t])), chain.last_error
    store.close()

    bpath = str(tmp_path / "cd" / "blocks.log")
    good = os.path.getsize(bpath)
    with open(bpath, "ab") as f:
        f.write(b"\xff\xff\xff\x7f partial-record-garbage")

    s2 = FileStore(str(tmp_path / "cd"))
    assert os.path.getsize(bpath) == good  # tear truncated
    c2 = BlockChain(store=s2, genesis=make_genesis(alloc=alloc),
                    alloc=alloc)
    assert c2.height() == 4
    # and the chain keeps extending after the repair
    t = signed_txn(PRIV_A, 4, ADDR_B, 1, gas_price=0)
    assert c2.offer(block_with(c2, [t])), c2.last_error
    assert c2.height() == 5
    s2.close()


def test_contract_storage_incremental_root_matches_batch_builder():
    """ContractStorage's incremental root must equal the from-scratch
    secure trie over the same pairs (VERDICT r3 #7), including deletes."""
    import random

    from eges_tpu.core import rlp as _rlp
    from eges_tpu.core.state import EMPTY_STORAGE
    from eges_tpu.core.trie import EMPTY_ROOT, secure_trie_root

    rng = random.Random(3)
    model = {}
    st = EMPTY_STORAGE
    for _ in range(30):
        writes = {}
        for _ in range(rng.randrange(1, 8)):
            slot = rng.randrange(0, 64)
            val = rng.choice([0, 0, rng.randrange(1, 2**80)])
            writes[slot] = val
        st = st.with_writes(writes)
        for k, v in writes.items():
            if v:
                model[k] = v
            else:
                model.pop(k, None)
        want = (secure_trie_root({
            s.to_bytes(32, "big"): _rlp.encode(v)
            for s, v in model.items()}) if model else EMPTY_ROOT)
        assert st.root() == want
        for k, v in model.items():
            assert st.get(k) == v
        assert st.get(999) == 0
    assert EMPTY_STORAGE.root() == EMPTY_ROOT  # untouched by history


def test_5k_slot_contract_sustains_per_block_writes():
    """The round-3 weakness: per-txn tuple rebuild + per-root full-trie
    rehash made a big contract quadratic.  Now: build 5k slots, then do
    50 'blocks' of 10-slot write-sets, each followed by a root — the
    per-block cost must stay bounded (measured ~ms; assert a generous
    ceiling so slow CI never flakes) and roots must track a model."""
    import time

    from eges_tpu.core.state import Account, StateDB

    addr = b"\x42" * 20
    s = StateDB({addr: Account(balance=1)})
    s.set_storage_many(addr, {i: i + 1 for i in range(5000)})
    s.root()

    t0 = time.monotonic()
    for blk in range(50):
        s = s.copy()
        s.set_storage_many(addr, {(blk * 97 + j) % 5000: blk * 1000 + j
                                  for j in range(10)})
        s.root()
    per_block = (time.monotonic() - t0) / 50
    assert per_block < 0.05, f"per-block storage cost {per_block:.3f}s"
    # reads see the latest writes through the overlay chain
    blk, j = 49, 3
    assert s.storage_at(addr, (blk * 97 + j) % 5000) == blk * 1000 + j
