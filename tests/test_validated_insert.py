"""An acceptor executes a block ONCE: ``BlockChain._insert`` takes the
state and receipts that ``validate_candidate`` computed and checked for
that very block (the IDENTICAL ``transactions`` tuple, on the head it was
validated on) and runs ``_verify_body`` / ``_process`` over anything else,
as before.  Host C++ verifier, tens of transfers a block.
"""

import dataclasses

import pytest

from eges_tpu.core.chain import BlockChain, ChainError, make_genesis
from eges_tpu.core.state import INTRINSIC_GAS
from eges_tpu.core.types import (
    Block, ConfirmBlockMsg, Header, Transaction, new_block,
)
from eges_tpu.crypto import secp256k1 as secp
from eges_tpu.crypto.verify_host import NativeBatchVerifier
from eges_tpu.utils import tracing
from eges_tpu.utils.metrics import DEFAULT as metrics

PRIVS = [bytes([0x31 + i]) * 32 for i in range(5)]
ADDRS = [secp.pubkey_to_address(secp.privkey_to_pubkey(p)) for p in PRIVS]
SINK = bytes([0xB0]) * 20
COINBASE = bytes([0xC0]) * 20
ALLOC = {a: 10**18 for a in ADDRS}
PER_SENDER = 4  # 20 transfers a block


def transfers(height: int, per_sender: int = PER_SENDER) -> list:
    """The block of ``height`` (from 1): every sender's next nonces."""
    return [Transaction(nonce=(height - 1) * per_sender + k, gas_price=1,
                        gas_limit=INTRINSIC_GAS, to=SINK,
                        value=1 + i).signed(priv, chain_id=1)
            for i, priv in enumerate(PRIVS) for k in range(per_sender)]


def mk_chain() -> BlockChain:
    return BlockChain(genesis=make_genesis(alloc=ALLOC), alloc=ALLOC,
                      verifier=NativeBatchVerifier())


def block_from(chain: BlockChain, preview, **header) -> Block:
    """The block a proposer builds on the chain's head from what its
    ``execute_preview`` returned."""
    kept, root, rroot, gas, bloom = preview
    parent = chain.head()
    fields = dict(parent_hash=parent.hash, number=parent.number + 1,
                  coinbase=COINBASE, time=parent.header.time + 1, root=root,
                  receipt_hash=rroot, gas_used=gas, bloom=bloom, trust_rand=1)
    fields.update(header)
    return new_block(Header(**fields), txs=kept)


def block_on(chain: BlockChain, txs, **header) -> Block:
    """A sound block of ``txs`` on the chain's head."""
    preview = chain.execute_preview(list(txs), COINBASE)
    assert len(preview[0]) == len(txs)
    return block_from(chain, preview, **header)


def confirm_of(block: Block) -> ConfirmBlockMsg:
    return ConfirmBlockMsg(block_number=block.number, hash=block.hash,
                           confidence=1000)


def counts() -> tuple[int, int]:
    return (metrics.counter("chain.insert_reused").value,
            metrics.counter("chain.executions").value)


def last_insert_span() -> dict:
    return [s for s in tracing.DEFAULT.finished()
            if s["name"] == "chain.insert"][-1]["attrs"]


def test_validate_then_insert_executes_the_block_once():
    chain = mk_chain()
    pending = block_on(chain, transfers(1))
    reused, executed = counts()  # after the proposer's own preview
    assert chain.validate_candidate(pending)
    assert list(chain._validated) == [pending.hash]
    sealed = pending.with_confirm(confirm_of(pending))
    assert sealed is not pending
    assert sealed.transactions is pending.transactions
    assert chain.offer(sealed) == [sealed]
    assert counts() == (reused + 1, executed + 1)
    assert last_insert_span() == {"number": 1, "txns": 20, "reused": 1}
    assert chain.head().hash == pending.hash and not chain._validated
    state = chain.head_state()
    assert state.root() == pending.header.root
    assert state.nonce(ADDRS[0]) == PER_SENDER
    assert state.balance(SINK) == PER_SENDER * sum(range(1, 6))


def test_two_chains_agree_whichever_path_inserted_their_blocks():
    src, validated, plain = mk_chain(), mk_chain(), mk_chain()
    reused, _ = counts()
    blocks = []
    for height in range(1, 7):
        blk = block_on(src, transfers(height))
        assert src.offer(blk)
        blocks.append(blk)
        assert validated.validate_candidate(blk)
        assert validated.offer(blk.with_confirm(confirm_of(blk)))
        # other bytes, another tuple: nothing of a validation to take
        assert plain.offer(Block.decode(blk.encode()).with_confirm(
            confirm_of(blk)))
        assert validated.head().hash == plain.head().hash == blk.hash
        assert validated.head_state().root() == plain.head_state().root() \
            == blk.header.root
    assert counts()[0] == reused + 6
    for blk in blocks:
        assert validated.receipts_of(blk.hash) == plain.receipts_of(blk.hash)
        assert len(validated.receipts_of(blk.hash)) == 20
        assert validated.store.get_receipts(blk.hash) == \
            plain.store.get_receipts(blk.hash)
        assert validated.store.get_block(blk.hash).confirm == confirm_of(blk)
        for i, t in enumerate(blk.transactions):
            got, want = validated.lookup_txn(t.hash), plain.lookup_txn(t.hash)
            assert got[0].hash == want[0].hash == blk.hash
            assert got[1:] == want[1:] == (
                i, validated.receipts_of(blk.hash)[i])
    assert validated.bloom_index.candidates(0, 6, [COINBASE], []) == \
        plain.bloom_index.candidates(0, 6, [COINBASE], [])


def _tampered(block: Block) -> Block:
    txs = list(block.transactions)
    txs[3], txs[4] = txs[4], txs[3]
    return dataclasses.replace(block, transactions=tuple(txs))


def _with_uncle(block: Block) -> Block:
    return dataclasses.replace(block, uncles=(block.header,))


@pytest.mark.parametrize("remade,error", [
    (_tampered, "transaction root mismatch"),
    (_with_uncle, "uncles not allowed"),
    (lambda b: Block.decode(b.encode()), None),
    (lambda b: dataclasses.replace(
        b, transactions=tuple(list(b.transactions))), None),
], ids=["tampered", "uncles", "redecoded", "equal_tuple"])
def test_a_body_that_is_not_the_validated_tuple_takes_the_full_path(
        remade, error):
    chain = mk_chain()
    pending = block_on(chain, transfers(1))
    assert chain.validate_candidate(pending)
    reused, executed = counts()
    other = remade(pending).with_confirm(confirm_of(pending))
    assert other.hash == pending.hash  # the hash covers the header alone
    if error is None:
        assert other.transactions == pending.transactions
        assert other.transactions is not pending.transactions
        assert chain.offer(other) == [other]
        assert counts() == (reused, executed + 1)
        assert last_insert_span()["reused"] == 0
        assert chain.head_state().root() == pending.header.root
        return
    assert chain.offer(other) == []
    assert chain.last_error == error and chain.bad_blocks == 1
    assert counts() == (reused, executed)  # refused before any execution
    assert chain.height() == 0
    assert chain.store.get_block(pending.hash) is None
    # the validated block itself is still good for this head
    assert chain.offer(pending.with_confirm(confirm_of(pending)))
    assert counts() == (reused + 1, executed)


def _wrong_state_root(chain):
    good = block_on(chain, transfers(1))
    return dataclasses.replace(good, header=dataclasses.replace(
        good.header, root=b"\xab" * 32))


def _bad_signature(chain):
    txs = transfers(1)
    txs[7] = dataclasses.replace(txs[7], r=txs[7].r ^ 1)
    parent = chain.head()
    return new_block(Header(parent_hash=parent.hash, number=1, time=1,
                            coinbase=COINBASE, root=parent.header.root,
                            trust_rand=1), txs=txs)


def _nonce_gap(chain):
    txs = transfers(1)
    del txs[1]  # the first sender's nonce 1: its 2 and 3 now gap
    parent = chain.head()
    return new_block(Header(parent_hash=parent.hash, number=1, time=1,
                            coinbase=COINBASE, root=parent.header.root,
                            trust_rand=1), txs=txs)


def _parent_unknown(chain):
    """Sound signatures on a parent this node has not seen: the
    signatures-only branch, which says yes and has executed nothing."""
    return new_block(Header(parent_hash=b"\x5a" * 32, number=9, time=9,
                            coinbase=COINBASE, trust_rand=1),
                     txs=transfers(1))


@pytest.mark.parametrize("make,said", [
    (_wrong_state_root, False), (_bad_signature, False),
    (_nonce_gap, False), (_parent_unknown, True),
], ids=["wrong_state_root", "bad_signature", "nonce_gap", "signatures_only"])
def test_only_a_full_validation_that_passed_leaves_an_entry(make, said):
    chain = mk_chain()
    assert chain.validate_candidate(make(chain)) is said
    assert chain._validated == {}


def test_a_head_that_moved_drops_the_entry_and_the_late_block():
    chain = mk_chain()
    late = block_on(chain, transfers(1))
    rival = block_on(chain, transfers(1), extra=b"rival")
    assert chain.validate_candidate(late)
    assert list(chain._validated) == [late.hash]
    assert chain.offer(rival.with_confirm(confirm_of(rival)))
    assert chain._validated == {}
    reused, executed = counts()
    sealed = late.with_confirm(confirm_of(late))
    assert chain.offer(sealed) == []  # an old height: dropped at the door
    with pytest.raises(ChainError, match="non-sequential insert"):
        chain._insert(sealed)
    assert counts() == (reused, executed)
    assert chain.head().hash == rival.hash


def test_replace_suffix_drops_the_entries():
    chain = mk_chain()
    b1 = block_on(chain, transfers(1))
    assert chain.offer(b1)
    assert chain.offer(chain.make_empty_block().with_confirm(ConfirmBlockMsg(
        block_number=2, hash=bytes(32), confidence=0, empty_block=True)))
    on_the_empty = block_on(chain, transfers(2))
    assert chain.validate_candidate(on_the_empty)
    assert list(chain._validated) == [on_the_empty.hash]
    # the quorum's block 2, built where this chain's height 1 stands
    twin = mk_chain()
    assert twin.offer(b1)
    real2 = block_on(twin, transfers(2))
    real2 = real2.with_confirm(confirm_of(real2))
    assert chain.replace_suffix([real2])
    assert chain._validated == {} and chain.head().hash == real2.hash
    assert last_insert_span()["reused"] == 0


def test_adopt_snapshot_drops_the_entries():
    src, dst = mk_chain(), mk_chain()
    for height in (1, 2, 3):
        assert src.offer(block_on(src, transfers(height)))
    assert dst.validate_candidate(src.get_block_by_number(1))
    assert len(dst._validated) == 1
    pivot = src.get_block_by_number(3)
    dst.adopt_snapshot(pivot, src.state_at(pivot.hash))
    assert dst._validated == {} and dst.height() == 3


def test_five_sound_candidates_of_a_height_leave_four_entries():
    chain = mk_chain()
    cands = [block_on(chain, transfers(1), extra=b"v%d" % i)
             for i in range(5)]
    assert len({c.hash for c in cands}) == 5
    for c in cands:
        assert chain.validate_candidate(c)
    assert len(chain._validated) == BlockChain._MAX_CANDIDATES == 4
    assert list(chain._validated) == [c.hash for c in cands[1:]]
    # validated again, the oldest that is left becomes the newest
    assert chain.validate_candidate(cands[1])
    assert list(chain._validated) == [c.hash for c in cands[2:] + cands[1:2]]
    # the one that fell out is inserted by the full path all the same
    reused, executed = counts()
    assert chain.offer(cands[0].with_confirm(confirm_of(cands[0])))
    assert counts() == (reused, executed + 1) and chain._validated == {}
