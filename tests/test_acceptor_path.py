"""The acceptor's half of ``consensus/node.py`` from BYTES, on the wall
clock, with no socket: what ``c1024a.blocks-backlog`` drives on the chip,
at a size a test can hold (16 validators, 32 transfers a block).

A ``GeecNode`` built as the benchmark's driver builds it
(``perfbench/drivers/acceptor.py build_node``) with a transport that keeps
what the node sends is handed the seeded chain of
``perfbench/gen_chain.py`` message by message through its own gossip entry
point.  Who may propose and who may certify is the plain copy of the
membership rules (``perfbench/ref/membership.py``), held to ``Membership``
here.
"""

import dataclasses
import random

import pytest

from eges_tpu.consensus import messages as M
from eges_tpu.consensus.membership import Member, Membership, derive_seed
from eges_tpu.core.chain import BlockChain
from eges_tpu.core.txpool import TxPool
from eges_tpu.crypto import secp256k1 as host
from eges_tpu.crypto.scheduler import VerifierScheduler
from eges_tpu.crypto.verify_host import NativeBatchVerifier
from eges_tpu.ingress import gossip_sink
from perfbench import gen_chain
from perfbench.drivers.acceptor import Transport, build_node
from perfbench.ref import membership as ref_members
from perfbench.ref import quorum as ref_quorum
from perfbench.ref import secp

DEPLOY = {"validators": 16, "committee": 4, "acceptors": 16,
          "txn_per_block": 32, "gossip_window": 8, "duplicate_share": 0.25,
          "unseen_share": 0.10, "invalid_every": 8, "accounts": 64,
          "senders": 16, "payload_bytes": 100, "gas_limit": 29000,
          "value_wei": 1, "balance_wei": 10**18, "bad_block_every": 2,
          "chain_blocks": 8}
SEED = 2**31 + 21


class Rig:
    """One node on a recording transport."""

    def __init__(self, feed):
        self.feed = feed
        self.sched = VerifierScheduler(NativeBatchVerifier(), max_batch=16)
        self.transport = Transport()
        self.chain = BlockChain(verifier=self.sched, alloc={
            a: feed.balance for a in feed.addrs})
        self.node = build_node(feed, DEPLOY, self.chain, self.sched,
                               self.transport)
        self.node.txpool = TxPool(self.node.clock, verifier=self.sched)
        self.sink = gossip_sink(self.node)

    def hand(self, data: bytes) -> list:
        """One message in; the validate replies that came out."""
        self.sink(data)
        return [a for a in (ref_quorum.read_ack(dg) for _ip, _port, dg
                            in self.transport.take_direct())
                if a is not None]

    def close(self) -> None:
        self.node.stop()
        self.sched.close()


@pytest.fixture()
def rig():
    r = Rig(gen_chain.ChainFeed(SEED, DEPLOY))
    yield r
    r.close()


def test_a_sound_block_is_acked_certified_and_inserted(rig):
    feed, node = rig.feed, rig.node
    request, confirm = feed.steps[1 - 1][-2:]  # height 1's sound pair
    for step in feed.steps[0]:
        if step is request:
            break
        rig.hand(step.data)  # the bad block in front of it, if any
    acks = rig.hand(request.data)
    assert len(acks) == 1
    author, num, accepted, bhash, sig = acks[0]
    # the node's own signature over THIS block's hash, by the reference
    assert (author, num, accepted, bhash) == (feed.node_addr, 1, 1,
                                              request.block_hash)
    assert secp.recover(ref_quorum.ack_sighash(1, author, 1, bhash),
                        sig) == feed.node_addr
    assert node.pending_blocks[1].hash == request.block_hash
    assert len(rig.transport.relayed) >= 1  # the request, relayed once
    assert rig.chain.height() == 0
    rig.hand(confirm.data)
    assert rig.chain.height() == 1
    assert rig.chain.head().hash == feed.block_hashes[0]
    assert node.max_confirmed_block == 1 and not node.pending_blocks


def test_the_whole_chain_goes_through_and_no_bad_block_is_acked(rig):
    feed = rig.feed
    seen = set()
    for p, steps in enumerate(feed.steps):
        for step in steps:
            acks = rig.hand(step.data)
            if step.what == "request":
                assert bool(acks) == step.sound, (p, step.bad)
                seen.add(step.bad)
            elif not step.sound:  # a genuine certificate of another hash
                assert rig.chain.height() == p
        assert rig.chain.height() == p + 1
        assert rig.chain.head().hash == feed.block_hashes[p]
    assert seen == {None, *gen_chain.BAD_KINDS}
    want, got = feed.state_at(len(feed.steps)), rig.chain.head_state()
    assert {a: (got.nonce(a), got.balance(a)) for a in want} == want


def _resigned(req: M.ValidateRequest, priv: int, **changes):
    req = dataclasses.replace(req, **changes)
    return dataclasses.replace(req, sig=host.ecdsa_sign(
        req.signing_hash(), priv.to_bytes(32, "big")))


def test_a_request_nobody_may_send_is_dropped_before_its_block_is_kept(rig):
    feed, node = rig.feed, rig.node
    step = feed.steps[0][0]
    _code, req = M.unpack_gossip(step.data)
    members = sorted(a for a, _ip, _port in feed.validators)
    outsider = next(a for a in members if a not in ref_members.committee(
        members, 0, req.version, DEPLOY["committee"]))
    # a sound signature by a validator who is not of the height's committee
    forged = _resigned(req, feed.priv_of[outsider], author=outsider)
    assert rig.hand(M.pack_gossip(M.GOSSIP_VALIDATE_REQ, forged)) == []
    # the committee member's name over a signature that is not its own
    stolen = dataclasses.replace(req, sig=forged.sig)
    assert rig.hand(M.pack_gossip(M.GOSSIP_VALIDATE_REQ, stolen)) == []
    broken = dataclasses.replace(req, sig=bytes(65))
    assert rig.hand(M.pack_gossip(M.GOSSIP_VALIDATE_REQ, broken)) == []
    assert node.pending_blocks == {} and rig.transport.relayed == []
    assert rig.chain.height() == 0


def test_a_certificate_short_or_of_another_hash_inserts_nothing(rig):
    feed, node = rig.feed, rig.node
    sound = [s for s in feed.steps[0] if s.sound]
    request, confirm = sound[-2:]
    for step in feed.steps[0]:
        if step is confirm:
            break
        rig.hand(step.data)
    assert rig.chain.height() == 0 and 1 in node.pending_blocks
    _code, msg = M.unpack_gossip(confirm.data)
    assert len(msg.supporters) == feed.need == \
        node.membership.validate_threshold() == 9
    short = dataclasses.replace(msg, supporters=msg.supporters[:-1],
                                supporter_sigs=msg.supporter_sigs[:-1])
    other = dataclasses.replace(msg, hash=bytes(32))
    for bad in (short, other):
        rig.hand(M.pack_gossip(M.GOSSIP_CONFIRM_BLOCK, bad))
        assert rig.chain.height() == 0 and node.max_confirmed_block == 0
    rig.hand(confirm.data)
    assert rig.chain.height() == 1


@pytest.mark.parametrize("seed", [0, 7, 2**63 + 5, 2**64 - 3])
@pytest.mark.parametrize("size,n", [(16, 4), (16, 16), (5, 8), (33, 32)])
def test_the_plain_copy_of_the_windows_is_the_memberships(seed, size, n):
    rng = random.Random(size * 1000 + n)
    addrs = sorted(rng.randbytes(20) for _ in range(size))
    ms = Membership(n, n)
    for a in addrs:
        ms.add(Member(addr=a, ip="", port=0))
    for version in (0, 1, 2):
        assert ref_members.derive_seed(seed, version) == \
            derive_seed(seed, version)
        want = set(ref_members.committee(addrs, seed, version, n))
        assert want == {m.addr for m in ms.committee(seed, version)}
        assert all(ms.is_committee(a, seed, version) == (a in want)
                   for a in addrs)
    want = set(ref_members.acceptors(addrs, seed, n))
    assert want == {m.addr for m in ms.acceptors(seed)}
    assert all(ms.is_acceptor(a, seed) == (a in want) for a in addrs)
    assert ref_members.majority(n, size) == ms.validate_threshold()


def _sim_journal(seed: int) -> bytes:
    """One short three-node cluster run's journal, as chaos compares it."""
    from eges_tpu.sim.cluster import SimCluster
    from harness.chaos import canonical_dump

    cluster = SimCluster(3, txn_per_block=4, seed=seed,
                         verifier=NativeBatchVerifier())
    cluster.start()
    cluster.run(600.0, stop_condition=lambda: cluster.min_height() >= 5)
    for sn in cluster.nodes:
        sn.node.stop()
    assert cluster.min_height() >= 5
    cluster.verifier.close()
    return canonical_dump(cluster.journals())


def test_the_new_spans_leave_a_sims_journal_byte_for_byte():
    """A span is not an event: one short cluster run's journal is the same
    with the block path's new spans and counters taken out."""
    from eges_tpu.utils import tracing
    from eges_tpu.utils.metrics import DEFAULT as metrics

    new = {"chain.validate_candidate", "chain.verify_body", "chain.execute",
           "state.root", "chain.receipts_root", "consensus.cert_ok"}
    assert new <= set(tracing.SPANS)

    before = metrics.counter("chain.executions").value
    with_spans = _sim_journal(44)
    assert metrics.counter("chain.executions").value > before
    real = tracing.Tracer.span

    def span(self, name, *a, **kw):
        if name in new:
            return tracing.Tracer.span(self, "test.left_out", *a, **kw)
        return real(self, name, *a, **kw)

    tracing.Tracer.span, saved = span, real
    try:
        without = _sim_journal(44)
    finally:
        tracing.Tracer.span = saved
    assert with_spans == without


def test_the_reused_validation_leaves_a_sims_journal_byte_for_byte(
        monkeypatch):
    """An insert that takes its own validation's state (an acceptor's; a
    preview's too, where this sim's proposers had transfers to preview) is
    the same insert: one short cluster run's journal is the same with the
    lookup made to find nothing, and the run WITH it did reuse."""
    from eges_tpu.utils.metrics import DEFAULT as metrics

    names = ("chain.insert_reused", "chain.insert_previewed",
             "chain.executions")

    def run() -> tuple:
        before = [metrics.counter(n).value for n in names]
        return (_sim_journal(45), *(metrics.counter(n).value - b
                                    for n, b in zip(names, before)))

    with_reuse, reused, previewed, executed = run()
    assert reused > 0
    monkeypatch.setattr(BlockChain, "_kept_outcome",
                        lambda self, block: None)
    without, none, nor_this, executed_twice = run()
    assert with_reuse == without
    # the same run, and every kept outcome taken was one execution the less
    assert none == nor_this == 0
    assert executed_twice == executed + reused + previewed
