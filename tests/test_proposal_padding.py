"""A block carries ``txn_per_block`` transactions IN ALL
(``consensus/node.py _build_proposal``): the unsigned fakes fill what the
UDP transactions and the signed transactions the preview kept leave.  With
an empty pool a block is padded as ever; with a full one it carries no
fake, so that a full block's validate request fits the 1 MiB decode budget
at which every acceptor of this program drops a datagram unread
(``GeecNode.INGRESS_MAX_BYTES``, which is NOT raised): at 4000 transfers a
block the request is acknowledged by a second node built from the same
genesis.
"""

import copy

import pytest

from eges_tpu.consensus.node import GeecNode
from eges_tpu.core.chain import BlockChain
from eges_tpu.core.txpool import TxPool
from eges_tpu.crypto.scheduler import VerifierScheduler
from eges_tpu.crypto.verify_host import NativeBatchVerifier
from eges_tpu.ingress import admit_remotes_window, decode_txn_window
from perfbench import gen_heights
from perfbench.drivers import acceptor
from perfbench.drivers import block_proposer as bp
from perfbench.ref import quorum as ref_quorum
from tests.test_proposer_path import DEPLOY

PER_BLOCK = DEPLOY["txn_per_block"]  # 32


class Rig:
    """One proposer with a pool, fed by hand."""

    def __init__(self, d, seed=2**31 + 41, max_batch=16):
        self.feed = feed = gen_heights.HeightsFeed(seed, d)
        self.sched = VerifierScheduler(NativeBatchVerifier(),
                                       max_batch=max_batch)
        self.transport = bp.Transport()
        self.chain = BlockChain(verifier=self.sched, alloc={
            a: feed.balance for a in feed.addrs})
        self.node = bp.build_node(feed, d, self.chain, self.sched,
                                  self.transport)
        self.pool = TxPool(self.node.clock, verifier=self.sched)
        self.node.txpool = self.pool

    def admit(self, count: int) -> None:
        """The stream's first ``count`` fresh transfers into the pool."""
        frames = self.feed.frames[:count]
        for i in range(0, len(frames), 256):
            admit_remotes_window(self.pool,
                                 decode_txn_window(frames[i:i + 256]))
        with self.pool._lock:
            self.pool._flush()
        assert self.pool.stats["admitted"] == count

    def close(self) -> None:
        self.node.stop()
        self.sched.close()


@pytest.mark.parametrize("signed, udp", [
    (0, 0),              # an empty pool: padded as ever
    (PER_BLOCK // 2, 0),  # half full: the fakes fill the other half
    (2 * PER_BLOCK, 0),  # full: no fake
    (0, 5), (PER_BLOCK // 2, 5),  # UDP transactions present
    (2 * PER_BLOCK, 5),  # beside a full pool nothing is left for a fake
])
def test_a_block_carries_txn_per_block_transactions_in_all(signed, udp):
    rig = Rig(DEPLOY)
    try:
        rig.admit(signed)
        for i in range(udp):
            rig.node.on_geec_txn(b"udp-%d" % i)
        with rig.node._lock:
            blk = rig.node._build_proposal(1)
        kept = len(blk.transactions)
        assert len(blk.geec_txns) == udp
        assert kept == (0 if not signed else
                        sum(1 for _ in rig.pool.pending_txns(
                            PER_BLOCK, state=rig.chain.head_state())))
        assert len(blk.fake_txns) == max(0, PER_BLOCK - udp - kept)
        if udp + kept <= PER_BLOCK:
            assert len(blk.geec_txns) + len(blk.fake_txns) + kept == \
                PER_BLOCK
        if signed >= PER_BLOCK:
            assert (kept, len(blk.fake_txns)) == (PER_BLOCK, 0)
        # the fakes ride beside the rooted body: the header does not know
        from eges_tpu.core.types import new_block
        assert new_block(blk.header, txs=blk.transactions,
                         geec_txns=blk.geec_txns).hash == blk.hash
    finally:
        rig.close()


def test_a_full_4000_transfer_request_fits_and_a_second_node_acks_it():
    d = {**DEPLOY, "txn_per_block": 4000, "gossip_window": 256,
         "invalid_every": 64, "accounts": 4096, "senders": 2048,
         "stream_heights": 1, "unexecutable_every": 16}
    assert GeecNode.INGRESS_MAX_BYTES == 1 << 20  # the DoS contract stands
    rig = Rig(d, max_batch=1024)
    other = None
    try:
        feed = rig.feed
        rig.admit(4000)
        rig.node.start()  # height 1's election; the committee answers
        for dg, _kind, _a in feed.votes[0]:
            rig.node.on_direct(dg)
        (request,) = [g for _t, g in rig.transport.gossiped
                      if bp._code(g) == bp.VALIDATE_REQ]
        req = bp.read_request(request)
        assert (len(req["tx_hashes"]), req["fakes"], req["geecs"]) == (
            4000, 0, 0)
        assert 780_000 < len(request) <= GeecNode.INGRESS_MAX_BYTES
        # upstream's padding, 4000 fakes of 100 B beside them, would not
        assert len(request) + 4000 * 100 > GeecNode.INGRESS_MAX_BYTES
        # a second validator of the same genesis: it executes the block
        # and ACKs this very hash with its own key
        theirs = copy.copy(feed)
        theirs.node_addr = next(a for a in feed.members
                                if a != feed.node_addr)
        theirs.node_priv = feed.priv_of[theirs.node_addr]
        sched = VerifierScheduler(NativeBatchVerifier(), max_batch=1024)
        transport = acceptor.Transport()
        chain = BlockChain(verifier=sched, alloc={
            a: feed.balance for a in feed.addrs})
        other = (acceptor.build_node(theirs, d, chain, sched, transport),
                 sched)
        other[0].on_gossip(request)
        (dg,) = [dg for _ip, _port, dg in transport.take_direct()
                 if ref_quorum.read_ack(dg) is not None]
        assert ref_quorum.sound_author(dg, feed.members, 1, req["hash"]) \
            == theirs.node_addr
        # and the proposer's own tally takes it
        rig.node.on_direct(dg)
        assert theirs.node_addr in rig.node.wb.validate_replies
    finally:
        rig.close()
        if other:
            other[0].stop()
            other[1].close()
