"""A block's execution against the benchmark's plain reference, block by
block: what ``c1024a.blocks-backlog`` holds the program to on the chip, at
a size a test can hold (8 blocks of 64 transfers over 256 accounts).

The seeded chain of ``perfbench/gen_chain.py`` carries the REFERENCE's
roots in every header (``perfbench/ref/state.py``: the transition, account
and receipt RLP, the secure trie and ``derive_sha`` a level at a time).
The program decodes each validate request off the wire, recovers the
senders through the scheduler (the host C++ verifier and the jax verifier
on the CPU backend, the 16-row bucket alone), executes the block on its
own parent state and must arrive at the same transaction root, state root,
receipts root, gas used and bloom, and at the same nonce and balance of
every account; ``validate_candidate`` must take every sound block and
refuse each kind of bad one.
"""

import pytest

from eges_tpu.consensus import messages as M
from eges_tpu.consensus.membership import Member, Membership
from eges_tpu.consensus.quorum import QuorumTally
from eges_tpu.core.chain import BlockChain
from eges_tpu.core.state import (process_block, receipts_bloom,
                                 receipts_root, recover_senders)
from eges_tpu.core.trie import derive_sha
from eges_tpu.crypto.scheduler import VerifierScheduler
from eges_tpu.crypto.verify_host import NativeBatchVerifier
from perfbench import gen_chain
from perfbench.ref import state as ref

DEPLOY = {"validators": 16, "committee": 4, "acceptors": 16,
          "txn_per_block": 64, "gossip_window": 16, "duplicate_share": 0.25,
          "unseen_share": 0.10, "invalid_every": 8, "accounts": 256,
          "senders": 32, "payload_bytes": 100, "gas_limit": 29000,
          "value_wei": 1, "balance_wei": 10**18, "bad_block_every": 2,
          "chain_blocks": 8}
MAX_BATCH = 16  # one bucket: the jax verifier compiles no other
_JAX = []


def _verifier(name: str):
    if name == "native":
        return NativeBatchVerifier()
    if not _JAX:
        import numpy as np

        from eges_tpu.crypto.verifier import BatchVerifier
        bv = BatchVerifier()
        # the 16-row bucket traces and compiles here (about a minute on
        # the CPU), not inside a wait of the play
        bv.recover_addresses(np.zeros((MAX_BATCH, 65), np.uint8),
                             np.zeros((MAX_BATCH, 32), np.uint8))
        _JAX.append(bv)
    return _JAX[0]


def play(feed, verifier) -> list:
    """The chain through the program, a height at a time; what each
    request's block came to, beside what the reference wrote."""
    sched = VerifierScheduler(verifier, max_batch=MAX_BATCH)
    members = Membership(DEPLOY["committee"], DEPLOY["acceptors"])
    for a, ip, port in feed.validators:
        members.add(Member(addr=a, ip=ip, port=port))
    tally = QuorumTally(members, sched)
    out = []
    try:
        chain = BlockChain(verifier=sched,
                           alloc={a: feed.balance for a in feed.addrs})
        assert chain.genesis.hash == feed.genesis_hash
        seed = 0
        for p, steps in enumerate(feed.steps):
            for step in steps:
                code, msg = M.unpack_gossip(step.data)
                if step.what == "confirm":
                    assert code == M.GOSSIP_CONFIRM_BLOCK
                    ok = tally.cert_ok(msg, seed)
                    out.append((p, "confirm", step.bad, ok))
                    if ok:
                        assert chain.offer(block.with_confirm(msg))
                    continue
                assert code == M.GOSSIP_VALIDATE_REQ
                block = msg.block
                assert block.hash == step.block_hash
                assert members.is_committee(msg.author, seed, msg.version)
                took = chain.validate_candidate(block)
                out.append((p, "request", step.bad, took))
                if not step.sound:
                    continue
                # the pieces of the validation, one by one, against the
                # header the reference wrote
                hdr, parent = feed.headers[p], chain.head_state()
                senders = recover_senders(block.transactions, sched)
                state, receipts, gas = process_block(parent, block, senders)
                assert derive_sha([t.encode() for t in block.transactions]) \
                    == hdr["tx_hash"] == block.header.tx_hash
                assert state.root() == hdr["root"] == block.header.root
                assert receipts_root(receipts) == hdr["receipt_hash"]
                assert gas == hdr["gas_used"] == 64 * ref.TX_GAS
                assert receipts_bloom(receipts) == hdr["bloom"]
                want = feed.state_at(p + 1)
                assert {a: (state.nonce(a), state.balance(a))
                        for a in want} == want
            assert chain.height() == p + 1
            assert chain.head().hash == feed.block_hashes[p]
            seed = chain.head().header.trust_rand
    finally:
        sched.close()
    return out


@pytest.mark.parametrize("name,seed", [
    ("native", 5), ("native", 2**31 + 7), ("native", 2**31 + 11),
    ("jax", 2**31 + 7)])
def test_the_program_executes_what_the_plain_reference_executes(name, seed):
    feed = gen_chain.ChainFeed(seed, DEPLOY)
    got = play(feed, _verifier(name))
    # every sound request taken, every sound certificate good
    assert all(ok for _p, _what, bad, ok in got if bad is None)
    assert sum(1 for _p, what, bad, _ok in got
               if what == "request" and bad is None) == 8


@pytest.mark.parametrize("kind", gen_chain.BAD_KINDS)
def test_each_kind_of_bad_block_is_refused(kind):
    feed = gen_chain.ChainFeed(2**31 + 13, DEPLOY, first_bad=kind)
    got = play(feed, NativeBatchVerifier())
    mine = [(what, ok) for _p, what, bad, ok in got if bad == kind]
    if kind == "certificate":
        # a sound block: taken; its confirm certifies ANOTHER hash: refused
        assert mine and all(ok == (what == "request") for what, ok in mine)
    else:
        assert mine and all(what == "request" and not ok
                            for what, ok in mine)
    assert {bad for _p, _w, bad, _ok in got} == {None, *gen_chain.BAD_KINDS}


def test_the_secure_state_is_the_whole_trie_built_anew():
    """The reference against itself: the trie that re-encodes what a block
    touched gives the root of the trie built whole, block after block."""
    feed = gen_chain.ChainFeed(9, DEPLOY)
    keys = dict(zip(feed.addrs, ref.keccak256_many(feed.addrs)))
    state = {a: [0, feed.balance] for a in feed.addrs}
    for p, delta in enumerate(feed.deltas):
        state.update({a: list(v) for a, v in delta.items()})
        assert ref.state_root(state, keys) == feed.headers[p]["root"]
    assert ref.trie_root([]) == ref.EMPTY_ROOT
    assert ref.logs_bloom([]) == ref.NO_BLOOM


def test_the_reference_refuses_what_upstream_refuses():
    a, b = b"\x01" * 20, b"\x02" * 20
    for bad in ((a, 1, b, 1, 29000),      # a nonce gap
                (a, 0, b, 11, 29000),     # more than the balance
                (a, 0, b, 1, 20999),      # under the intrinsic gas
                (b, 0, a, 1, 29000)):     # an account nobody funded
        with pytest.raises(ref.Refused):
            ref.apply_transfers({a: [0, 10]}, [bad])
    state = {a: [0, 10]}
    touched, gas = ref.apply_transfers(state, [(a, 0, b, 1, 29000),
                                               (a, 1, a, 2, 29000)])
    assert state == {a: [2, 9], b: [0, 1]} and touched == {a, b}
    assert gas == [ref.TX_GAS, 2 * ref.TX_GAS]
