"""A proposer executes its own block ONCE: ``BlockChain.execute_preview``
keeps the state and receipts it computed, and ``_insert`` takes them for
the block built from that very tuple of transactions on that very head
under that very coinbase and ctx with those very commitments in its header
(``_verify_body`` still ties the body to the header), and runs
``_verify_body`` / ``_process`` over anything else, as before.  Host C++
verifier, 20 transfers a block; beside ``tests/test_validated_insert.py``,
whose chains and blocks these are.
"""

import dataclasses

import pytest

from eges_tpu.core.chain import BlockChain, ChainError
from eges_tpu.core.evm import BlockCtx
from eges_tpu.core.state import INTRINSIC_GAS
from eges_tpu.core.types import Block, ConfirmBlockMsg, Transaction
from eges_tpu.crypto import secp256k1 as secp
from eges_tpu.utils.metrics import DEFAULT as metrics
from tests.test_validated_insert import (
    ADDRS, ALLOC, COINBASE, PER_SENDER, PRIVS, SINK, block_from, block_on,
    confirm_of, last_insert_span, mk_chain, transfers,
)


def counts() -> tuple[int, int, int]:
    return (metrics.counter("chain.insert_previewed").value,
            metrics.counter("chain.insert_reused").value,
            metrics.counter("chain.executions").value)


def sealed(block: Block) -> Block:
    return block.with_confirm(confirm_of(block))


def test_preview_then_insert_executes_the_block_once():
    chain = mk_chain()
    previewed, reused, executed = counts()
    preview = chain.execute_preview(transfers(1), COINBASE)
    kept, root = preview[:2]
    assert isinstance(kept, tuple)
    assert chain._previewed.transactions is kept
    assert chain._previewed.head == chain.head().hash
    pending = block_from(chain, preview)
    assert pending.transactions is kept
    block = sealed(pending)
    assert block is not pending and block.transactions is kept
    assert chain.offer(block) == [block]
    assert counts() == (previewed + 1, reused, executed + 1)
    assert last_insert_span() == {"number": 1, "txns": 20, "reused": 1}
    assert chain.head().hash == pending.hash and chain._previewed is None
    state = chain.head_state()
    assert state.root() == root == pending.header.root
    assert state.nonce(ADDRS[0]) == PER_SENDER
    assert state.balance(SINK) == PER_SENDER * sum(range(1, 6))
    assert state.balance(COINBASE) == 20 * INTRINSIC_GAS
    assert len(chain.receipts_of(block.hash)) == 20


def test_two_chains_agree_whichever_path_inserted_their_blocks():
    previewing, plain = mk_chain(), mk_chain()
    previewed, _, executed = counts()
    blocks = []
    for height in range(1, 7):
        blk = sealed(block_on(previewing, transfers(height)))
        assert previewing.offer(blk) == [blk]
        assert last_insert_span()["reused"] == 1
        blocks.append(blk)
        # other bytes, another tuple: nothing of a preview to take
        assert plain.offer(Block.decode(blk.encode()))
        assert last_insert_span()["reused"] == 0
        assert previewing.head().hash == plain.head().hash == blk.hash
        assert previewing.head_state().root() == plain.head_state().root() \
            == blk.header.root
    # six previews and six executing inserts, six inserts that took a preview
    assert counts() == (previewed + 6, counts()[1], executed + 12)
    for blk in blocks:
        assert previewing.receipts_of(blk.hash) == plain.receipts_of(blk.hash)
        assert len(previewing.receipts_of(blk.hash)) == 20
        assert previewing.store.get_receipts(blk.hash) == \
            plain.store.get_receipts(blk.hash)
        assert previewing.store.get_block(blk.hash).confirm == confirm_of(blk)
        for i, t in enumerate(blk.transactions):
            got, want = previewing.lookup_txn(t.hash), plain.lookup_txn(t.hash)
            assert got[0].hash == want[0].hash == blk.hash
            assert got[1:] == want[1:] == (
                i, previewing.receipts_of(blk.hash)[i])
    assert previewing.bloom_index.candidates(0, 6, [COINBASE], []) == \
        plain.bloom_index.candidates(0, 6, [COINBASE], [])
    for a in ADDRS + [SINK, COINBASE]:
        assert previewing.head_state().account(a) == \
            plain.head_state().account(a)


# Every way out of (and one way through) the preview's path.  A case builds
# the block it offers on a fresh chain and says how the offer ends:
# ``(block, inserted, last_error, previews taken, executions)``.

def _header(block: Block, **fields) -> Block:
    """The same body, the IDENTICAL tuple, under another header."""
    return dataclasses.replace(block, header=dataclasses.replace(
        block.header, **fields))


def _redecoded(chain):
    return Block.decode(block_on(chain, transfers(1)).encode()), \
        True, None, 0, 1


def _equal_tuple(chain):
    blk = block_on(chain, transfers(1))
    return dataclasses.replace(
        blk, transactions=tuple(list(blk.transactions))), True, None, 0, 1


def _wrong(field, value, error, executed=1):
    def case(chain):
        return _header(block_on(chain, transfers(1)), **{field: value}), \
            False, error, 0, executed
    return case


def _other_ctx(field, value, error=None):
    """The header says another ctx than the preview executed with: the
    full path, which inserts a sound block and refuses an unsound one."""
    def case(chain):
        return block_on(chain, transfers(1), **{field: value}), \
            error is None, error, 0, 1
    return case


def _ctx_with_blockhash(chain):
    parent = chain.head()
    preview = chain.execute_preview(transfers(1), COINBASE, ctx=BlockCtx(
        coinbase=COINBASE, number=1, time=parent.header.time + 1,
        blockhash=lambda n: bytes(32)))
    return block_from(chain, preview), True, None, 0, 1


def _second_preview(chain):
    first = block_on(chain, transfers(1))
    second = block_on(chain, transfers(1), extra=b"second")
    assert chain._previewed.transactions is second.transactions
    assert first.transactions == second.transactions
    return first, True, None, 0, 1


def _head_moved(chain):
    late = block_on(chain, transfers(1))
    rival = block_on(chain, transfers(1), extra=b"rival")
    assert chain.offer(sealed(rival)) and chain._previewed is None
    with pytest.raises(ChainError, match="non-sequential insert"):
        chain._insert(sealed(late))
    return late, False, None, 0, 0  # an old height: dropped at the door


def _replace_suffix(chain):
    b1 = block_on(chain, transfers(1))
    assert chain.offer(b1)
    assert chain.offer(chain.make_empty_block().with_confirm(ConfirmBlockMsg(
        block_number=2, hash=bytes(32), confidence=0, empty_block=True)))
    on_the_empty = block_on(chain, transfers(2))
    assert chain._previewed.transactions is on_the_empty.transactions
    # the quorum's block 2, built where this chain's height 1 stands
    twin = mk_chain()
    assert twin.offer(Block.decode(b1.encode()))
    real2 = sealed(block_on(twin, transfers(2)))
    assert chain.replace_suffix([real2])
    assert chain._previewed is None and chain.head().hash == real2.hash
    assert last_insert_span()["reused"] == 0
    return on_the_empty, False, "unknown ancestor", 0, 0


def _adopt_snapshot(chain):
    src = mk_chain()
    for height in (1, 2, 3):
        assert src.offer(block_on(src, transfers(height)))
    early = block_on(chain, transfers(1))
    assert chain._previewed is not None
    pivot = src.get_block_by_number(3)
    chain.adopt_snapshot(pivot, src.state_at(pivot.hash))
    assert chain._previewed is None and chain.height() == 3
    return early, False, None, 0, 0


def _dropped_unexecutable(chain):
    """Two of 22 cannot execute: the KEPT tuple is what the block roots
    and what the insert takes."""
    txs = transfers(1)
    gap = Transaction(nonce=PER_SENDER + 3, gas_price=1,
                      gas_limit=INTRINSIC_GAS, to=SINK,
                      value=1).signed(PRIVS[0], chain_id=1)
    poor = Transaction(nonce=PER_SENDER, gas_price=1,
                       gas_limit=INTRINSIC_GAS, to=SINK,
                       value=10**19).signed(PRIVS[1], chain_id=1)
    txs[5:5] = [gap]
    txs.append(poor)
    dropped = metrics.counter("chain.preview_dropped").value
    preview = chain.execute_preview(txs, COINBASE)
    assert metrics.counter("chain.preview_dropped").value == dropped + 2
    assert [t.hash for t in preview[0]] == [t.hash for t in transfers(1)]
    assert chain._previewed.transactions is preview[0]
    return block_from(chain, preview), True, None, 1, 0


CASES = {
    "redecoded": _redecoded,
    "equal_tuple": _equal_tuple,
    "wrong_root": _wrong("root", b"\xab" * 32, "state root mismatch"),
    "wrong_receipt_hash": _wrong("receipt_hash", b"\xab" * 32,
                                 "receipt root mismatch"),
    "wrong_gas_used": _wrong("gas_used", 20 * INTRINSIC_GAS + 1,
                             "gas used mismatch"),
    "wrong_bloom": _wrong("bloom", b"\x01" + bytes(255),
                          "log bloom mismatch"),
    # the preview's path roots the body too: refused before any execution
    "wrong_tx_hash": _wrong("tx_hash", b"\xab" * 32,
                            "transaction root mismatch", executed=0),
    "other_time": _other_ctx("time", 77),
    "other_difficulty": _other_ctx("difficulty", 9),
    # the fees went to the preview's coinbase, not to the header's
    "other_coinbase": _other_ctx("coinbase", bytes([0xC1]) * 20,
                                 "state root mismatch"),
    "other_gas_limit": _other_ctx("gas_limit", 8_000_000),
    "ctx_with_blockhash": _ctx_with_blockhash,
    "second_preview": _second_preview,
    "head_moved": _head_moved,
    "replace_suffix": _replace_suffix,
    "adopt_snapshot": _adopt_snapshot,
    "dropped_unexecutable": _dropped_unexecutable,
}


def _offer(case) -> tuple:
    chain = mk_chain()
    block, inserted, error, taken, executions = case(chain)
    height, bad = chain.height(), chain.bad_blocks
    previewed, reused, executed = counts()
    got = chain.offer(sealed(block))
    assert got == ([sealed(block)] if inserted else [])
    assert chain.height() == height + inserted
    assert chain.bad_blocks == bad + (error is not None)
    if error is not None:
        assert chain.last_error == error
        assert chain.store.get_block(block.hash) is None
    if inserted:
        assert last_insert_span()["reused"] == taken
        assert chain.head_state().root() == block.header.root
        assert chain._previewed is None
    assert counts() == (previewed + taken, reused, executed + executions)
    return (inserted, chain.last_error, chain.bad_blocks, chain.head().hash,
            chain.head_state().root(), chain.receipts_of(chain.head().hash))


@pytest.mark.parametrize("name", list(CASES))
def test_every_way_past_the_previews_path_ends_as_it_did(name, monkeypatch):
    ended = _offer(CASES[name])
    # as today: the same case on a chain that keeps no outcome at all
    monkeypatch.setattr(BlockChain, "_kept_outcome", lambda self, b: None)
    chain = mk_chain()
    block, inserted, error, _taken, _executions = CASES[name](chain)
    assert bool(chain.offer(sealed(block))) is inserted
    assert ended == (inserted, chain.last_error, chain.bad_blocks,
                     chain.head().hash, chain.head_state().root(),
                     chain.receipts_of(chain.head().hash))
    assert chain.last_error == error


def test_a_refused_header_leaves_the_preview_for_the_sound_block():
    chain = mk_chain()
    good = block_on(chain, transfers(1))
    assert chain.offer(sealed(_header(good, root=b"\xab" * 32))) == []
    assert chain.last_error == "state root mismatch"
    previewed, reused, executed = counts()
    assert chain.offer(sealed(good)) == [sealed(good)]
    assert counts() == (previewed + 1, reused, executed)


def test_a_validation_of_the_previewed_block_is_the_one_taken():
    """Both keeps hold this body: the validation's answers, and the
    acceptor's counter keeps its meaning."""
    chain = mk_chain()
    pending = block_on(chain, transfers(1))
    assert chain.validate_candidate(pending)
    assert chain._previewed.transactions is pending.transactions
    previewed, reused, executed = counts()
    assert chain.offer(sealed(pending))
    assert counts() == (previewed, reused + 1, executed)
    assert chain._previewed is None and chain._validated == {}


def test_a_preview_that_kept_nothing_keeps_nothing():
    """``()`` is every empty body's tuple: its identity names no body."""
    chain = mk_chain()
    block_on(chain, transfers(1))
    assert chain._previewed is not None
    gap = Transaction(nonce=9, gas_price=1, gas_limit=INTRINSIC_GAS, to=SINK,
                      value=1).signed(PRIVS[0], chain_id=1)
    preview = chain.execute_preview([gap], COINBASE)
    assert preview[0] == () and chain._previewed is None
    assert preview[1] == chain.head().header.root and preview[3] == 0
    previewed, reused, executed = counts()
    empty = block_from(chain, preview)
    assert chain.offer(sealed(empty)) == [sealed(empty)]
    assert counts() == (previewed, reused, executed + 1)


def test_the_engines_that_seal_their_own_block_execute_it_once():
    from eges_tpu.core.chain import make_genesis
    from eges_tpu.core.engine import DevEngine
    from eges_tpu.crypto.verify_host import NativeBatchVerifier

    authority = secp.pubkey_to_address(secp.privkey_to_pubkey(PRIVS[0]))
    engine = DevEngine(authority, PRIVS[0])
    chain = BlockChain(genesis=make_genesis(alloc=ALLOC), alloc=ALLOC,
                       verifier=NativeBatchVerifier(), engine=engine)
    previewed, reused, executed = counts()
    for height in (1, 2, 3):
        block = engine.seal_next(chain, transfers(height))
        assert chain.head().hash == block.hash
    assert counts() == (previewed + 3, reused, executed + 3)


def test_previews_from_another_thread_never_lend_their_state():
    """One thread builds and offers a chain's blocks while another keeps
    previewing on the same chain (an RPC's dry run beside the proposer):
    the slot is written and read under the chain's lock, so a block takes
    its OWN preview's state or is executed in full, and every height's
    state is its header's."""
    import sys
    import threading

    chain, heights = mk_chain(), 12
    stop, failed = threading.Event(), []

    def meddle():
        other = bytes([0xC7]) * 20
        try:
            while not stop.is_set():
                chain.execute_preview(
                    transfers(chain.height() + 1)[:7], other)
        except Exception as e:  # the test's own boundary
            failed.append(repr(e))

    previewed, reused, executed = counts()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    t = threading.Thread(target=meddle)
    t.start()
    try:
        for height in range(1, heights + 1):
            blk = sealed(block_on(chain, transfers(height)))
            assert chain.offer(blk) == [blk], chain.last_error
            state = chain.head_state()
            assert state.root() == blk.header.root
            assert state.nonce(ADDRS[0]) == height * PER_SENDER
            assert state.balance(COINBASE) == height * 20 * INTRINSIC_GAS
    finally:
        stop.set()
        t.join(30.0)
        sys.setswitchinterval(interval)
    assert not t.is_alive() and failed == []
    took = counts()[0] - previewed
    # a block whose preview was overwritten in between was executed again
    assert 0 <= took <= heights and counts()[1] == reused
    assert chain.height() == heights and chain.bad_blocks == 0
