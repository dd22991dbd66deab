"""Fast-sync (state sync) tests — the statesync.go role (r5 verdict
item 7): serialization round-trips, pivot adoption + restart anchoring,
and the end-to-end sim: a late joiner catches a running chain's head in
O(state) + O(tail), with the pre-pivot ancestry verifiably ABSENT."""

import os

import pytest

from eges_tpu.core import rlp
from eges_tpu.core import statesync as ss
from eges_tpu.core.chain import BlockChain, FileStore, make_genesis
from eges_tpu.core.state import StateDB
from eges_tpu.core.types import Header, Transaction, new_block
from eges_tpu.crypto import secp256k1 as secp
from eges_tpu.sim.cluster import SimCluster
from tests.test_trie_native import trie_rung  # noqa: F401 (a fixture)

# every case on both rungs of the persistent trie: the library's node
# store and the Python nodes (tests/test_trie_native.py old_library)
pytestmark = pytest.mark.usefixtures("trie_rung")

PRIV = bytes([3]) * 32
ADDR = secp.pubkey_to_address(secp.privkey_to_pubkey(PRIV))
ETH = 10**18


def _grow(chain, n_blocks, start_nonce=0):
    """Extend ``chain`` with value-transfer blocks (distinct states)."""
    nonce = start_nonce
    for _ in range(n_blocks):
        head = chain.head()
        t = Transaction(nonce=nonce, gas_price=0, gas_limit=21_000,
                        to=bytes([nonce % 250 + 1]) * 20,
                        value=1).signed(PRIV)
        nonce += 1
        kept, root, rroot, gas, bloom = chain.execute_preview(
            [t], coinbase=bytes(20))
        blk = new_block(Header(parent_hash=head.hash,
                               number=head.number + 1,
                               time=head.header.time + 1, root=root,
                               receipt_hash=rroot, gas_used=gas,
                               bloom=bloom), txs=kept)
        assert chain.offer(blk), chain.last_error
    return nonce


def test_snapshot_roundtrip_detects_tampering():
    s = StateDB.from_alloc({ADDR: 10 * ETH})
    s.set_code(b"\xbb" * 20, b"\x60\x01\x00")
    s.set_storage_many(b"\xbb" * 20, {i: i + 1 for i in range(40)})
    accs = ss.snapshot_accounts(s)
    codes = ss.codes_for(s, accs)
    rebuilt = ss.assemble(accs, codes)
    assert rebuilt.root() == s.root()
    assert rebuilt.storage_at(b"\xbb" * 20, 7) == 8
    # tamper with one slot value -> root diverges (nothing is trusted)
    a, n, b, ch, slots = accs[-1]
    bad = accs[:-1] + [(a, n, b, ch, slots[:-1])]
    assert ss.assemble(bad, codes).root() != s.root()
    # swap the code blob -> code_hash re-derives -> root diverges
    assert ss.assemble(accs, (b"\x60\x02\x00",)).root() != s.root()


def test_adopt_snapshot_and_restart_anchor(tmp_path):
    alloc = {ADDR: 10 * ETH}
    genesis = make_genesis(alloc=alloc)
    src = BlockChain(genesis=genesis, alloc=alloc)
    nonce = _grow(src, 10)

    # joiner adopts pivot 8 without blocks 1..7, then replays the tail
    pivot = src.get_block_by_number(8)
    pivot_state = src.state_at(pivot.hash)
    store = FileStore(str(tmp_path / "joiner"))
    dst = BlockChain(store=store, genesis=genesis, alloc=alloc)
    dst.adopt_snapshot(pivot, pivot_state)
    assert dst.height() == 8
    assert dst.get_block_by_number(3) is None        # no ancestry
    for n in (9, 10):
        assert dst.offer(src.get_block_by_number(n)), dst.last_error
    assert dst.height() == 10
    assert dst.head_state().root() == src.head_state().root()

    # restart: the snapshot sidecar anchors the replay where the
    # missing ancestors would otherwise crash it (SURVEY §5 resume)
    store.close()
    dst2 = BlockChain(store=FileStore(str(tmp_path / "joiner")),
                      genesis=genesis, alloc=alloc)
    assert dst2.height() == 10
    assert dst2.head_state().root() == src.head_state().root()
    assert dst2.state_at(pivot.hash) is not None


def test_sim_late_joiner_fast_syncs():
    # 3 validators run ahead; node3 joins late with --syncmode fast:
    # it must adopt a pivot state (no pre-pivot blocks) and catch up
    c = SimCluster(4, n_bootstrap=3, txn_per_block=2, seed=11,
                   reg_timeout_s=5.0, defer={3}, fast_sync={3})
    joiner = c.nodes[3]
    joiner.node.FASTSYNC_MIN_GAP = 16    # sim chains are short
    c.start()
    c.run(900, stop_condition=lambda: min(
        sn.chain.height() for sn in c.nodes[:3]) >= 60)
    assert min(sn.chain.height() for sn in c.nodes[:3]) >= 60

    c.start_deferred(3)
    c.run(900, stop_condition=lambda: (
        joiner.node._fs_done
        and joiner.chain.height() >= c.nodes[0].chain.height() - 4))
    assert joiner.node._fs_done
    head = c.nodes[0].chain.height()
    assert joiner.chain.height() >= head - 4, (
        joiner.chain.height(), head)
    # fast sync REALLY happened: the joiner never downloaded the early
    # chain — O(state), not O(chain)
    assert joiner.chain.get_block_by_number(1) is None
    # and its head state agrees with a validator's at the same height
    h = min(joiner.chain.height(), c.nodes[0].chain.height())
    b_j = joiner.chain.get_block_by_number(h)
    b_v = c.nodes[0].chain.get_block_by_number(h)
    assert b_j.hash == b_v.hash
    assert joiner.chain.state_at(b_j.hash).root() == b_j.header.root


def _rich_state() -> StateDB:
    s = StateDB.from_alloc({ADDR: 10 * ETH,
                            b"\xaa" * 20: 7, b"\xcc" * 20: 9})
    s.set_code(b"\xbb" * 20, b"\x60\x01\x00")
    s.set_storage_many(b"\xbb" * 20, {i: i + 1 for i in range(8)})
    return s


def test_checkpoint_roundtrip_with_consensus():
    s = _rich_state()
    cons = {
        "members": [(bytes([7]) * 20, bytes([8]) * 20, "10.0.0.7",
                     4107, 3, 120, 2)],
        "trust_rands": [(0, 0), (5, 1234)],
        "empty_blocks": [2, 9],
        "unconfirmed": [11],
        "registered": True,
    }
    blob = ss.encode_checkpoint(b"\x11" * 32, s, consensus=cons)
    bh, state, got = ss.decode_checkpoint(blob)
    assert bh == b"\x11" * 32
    assert state.root() == s.root()
    assert got == cons
    # the legacy (fast-sync adopt) shape still decodes, with no
    # consensus section — either sidecar generation boots either node
    bh2, state2, got2 = ss.decode_checkpoint(
        ss.encode_snapshot(b"\x22" * 32, s))
    assert bh2 == b"\x22" * 32
    assert state2.root() == s.root()
    assert got2 is None


def test_checkpoint_corruption_fuzz():
    """Every mutation of a checkpoint sidecar must either raise
    StateSyncError or visibly shift the rebuilt identity — a damaged
    sidecar is NEVER silently adoptable as the original."""
    s = _rich_state()
    blob = ss.encode_checkpoint(b"\x33" * 32, s)
    ref_root = s.root()

    # truncation at every stride, including the empty blob
    for cut in range(0, len(blob) - 1, max(1, len(blob) // 23)):
        with pytest.raises(ss.StateSyncError):
            ss.decode_checkpoint(blob[:cut])

    # deterministic single-bit flips across the whole blob: the body
    # checksum (or the rlp framing) must catch every one of them
    for pos in range(0, len(blob), max(1, len(blob) // 47)):
        bad = bytearray(blob)
        bad[pos] ^= 0x40
        try:
            bh, state, cons = ss.decode_checkpoint(bytes(bad))
        except ss.StateSyncError:
            continue
        assert (bh, state.root()) != (b"\x33" * 32, ref_root)


def test_legacy_snapshot_corruption_fuzz():
    """The unchecksummed legacy shape relies on end-to-end structure:
    wrong code blobs shift the rebuilt root, duplicate or unsorted
    accounts trip the strict ordering invariant."""
    s = _rich_state()
    accounts = ss.snapshot_accounts(s)
    codes = list(ss.codes_for(s, accounts))
    enc = ss._encode_accounts(accounts)

    # wrong code blob: decodes, but code_hash re-derives -> root shifts
    _bh, state, _ = ss.decode_checkpoint(
        rlp.encode([b"\x44" * 32, enc, [b"\x60\x02\x00"]]))
    assert state.root() != s.root()

    # duplicated account entry
    with pytest.raises(ss.StateSyncError):
        ss.decode_checkpoint(
            rlp.encode([b"\x44" * 32, enc + [enc[0]], codes]))
    # unsorted (reversed) account list
    with pytest.raises(ss.StateSyncError):
        ss.decode_checkpoint(
            rlp.encode([b"\x44" * 32, list(reversed(enc)), codes]))


def test_staged_page_roundtrip_and_corruption():
    s = _rich_state()
    accounts = ss.snapshot_accounts(s)
    codes = list(ss.codes_for(s, accounts))
    blob = ss.encode_page(9, b"\xee" * 32, 2, 7, accounts, codes)
    pivot, root, cursor, total, accs, cds = ss.decode_page(blob)
    assert (pivot, root, cursor, total) == (9, b"\xee" * 32, 2, 7)
    assert accs == accounts
    assert cds == codes
    for cut in (0, 1, len(blob) // 2, len(blob) - 1):
        with pytest.raises(ss.StateSyncError):
            ss.decode_page(blob[:cut])


def test_filestore_sync_staging_roundtrip_and_torn_tail(tmp_path):
    store = FileStore(str(tmp_path / "n"))
    p1, p2 = b"page-one", b"page-two-longer"
    store.append_sync_page(p1)
    store.append_sync_page(p2)
    assert store.load_sync_pages() == [p1, p2]
    # torn tail (a crash mid-append): a truncated length prefix, then a
    # full prefix with a missing payload — the loader keeps the prefix
    log = os.path.join(str(tmp_path / "n"), "sync_pages.log")
    with open(log, "ab") as fh:
        fh.write((1 << 20).to_bytes(4, "big") + b"xx")
    assert store.load_sync_pages() == [p1, p2]
    store.clear_sync_staging()
    assert store.load_sync_pages() == []
    assert not os.path.exists(log)
    store.close()


def test_checkpointed_restart_replays_only_tail():
    # the O(tail) rejoin contract, unit-scale: crash one node, let the
    # survivors run ahead, restart it — the boot must anchor on the
    # newest durable checkpoint and replay only the tail past it
    c = SimCluster(4, seed=3, txn_per_block=2, checkpoint_every=4)
    c.start()
    c.run(900, stop_condition=lambda: c.min_height() >= 12)
    c.crash(1)
    c.run(240, stop_condition=lambda: min(
        sn.chain.height() for sn in c.live_nodes()) >= 16)
    c.restart(1)
    rst = [e for e in c.journals().get("node1", [])
           if e.get("type") == "statesync_restart"]
    assert rst, "restart never journaled a statesync_restart event"
    ev = rst[-1]
    assert ev["snapshot_blk"] > 0
    assert ev["replayed"] <= ev["blk"] - ev["snapshot_blk"]
    assert ev["replayed"] < ev["blk"]          # O(tail), not O(chain)
    for sn in c.live_nodes():
        sn.node.stop()


def test_unsigned_chain_falls_back_to_full_replay():
    # without signed votes there is no certificate to trust a pivot
    # root against: the fast_sync flag must be inert, full sync works
    c = SimCluster(4, n_bootstrap=3, txn_per_block=2, seed=7,
                   signed=False, reg_timeout_s=5.0, defer={3},
                   fast_sync={3})
    joiner = c.nodes[3]
    joiner.node.FASTSYNC_MIN_GAP = 8
    c.start()
    c.run(600, stop_condition=lambda: min(
        sn.chain.height() for sn in c.nodes[:3]) >= 25)
    c.start_deferred(3)
    c.run(600, stop_condition=lambda: (
        joiner.chain.height() >= c.nodes[0].chain.height() - 3))
    assert joiner.chain.height() >= c.nodes[0].chain.height() - 3
    assert not joiner.node._fs_done          # fast sync never engaged
    assert joiner.chain.get_block_by_number(1) is not None  # full replay
