"""The trusted random source (Geec's THW) is a dependency of the node, as
its clock and its transport are: ``GeecNode(rand_source=...)`` /
``WorkingBlock(coinbase, rand_source)``.

With the DEFAULT source (``working_block.CoinbaseRand``: the PRNG seeded
by the coinbase, a draw a ``WorkingBlock.advance`` and a draw a build) the
simulator's journals are byte for byte what they were before the source
could be given: a recorded digest of a three-node sim's journals and heads
(taken on the tree before the change).  With a GIVEN source the committee
of each height is the one ``perfbench/ref/membership.py`` derives from the
seeds handed in.
"""

import hashlib
import json
import random

from eges_tpu.consensus.working_block import CoinbaseRand, WorkingBlock
from eges_tpu.sim.cluster import SimCluster
from perfbench.ref import membership as ref_members

# sha256 over every node's journal (each event as sorted JSON, without its
# trace id and without block_committed's wall-clock ``dt``) and head hash:
# SimCluster(3, txn_per_block=4, seed=17), three UDP transactions at node
# 0, three virtual seconds; 217 heights.  Recorded on commit ca586ce.
RECORDED = "b69467f048e27ed7986c7b4d3e339fd7699c733e20e16f6901bf35aacb0a4c1e"


def _digest(cluster) -> str:
    h = hashlib.sha256()
    for sn in cluster.nodes:
        for ev in sn.node.journal.events():
            ev = {k: v for k, v in ev.items() if k != "trace"
                  and not (k == "dt" and ev["type"] == "block_committed")}
            h.update(json.dumps(ev, sort_keys=True).encode())
        h.update(sn.chain.head().hash)
    return h.hexdigest()


def test_the_default_source_leaves_the_sims_journals_byte_for_byte():
    c = SimCluster(3, txn_per_block=4, seed=17)
    c.start()
    for i in range(3):
        c.nodes[0].node.on_geec_txn(b"txn-%d" % i)
    c.run(3.0)
    assert c.heights() == [217, 217, 217]
    assert _digest(c) == RECORDED


def test_the_default_source_is_the_coinbases_prng_draw_for_draw():
    coinbase = bytes(range(1, 21))
    rng = random.Random(int.from_bytes(coinbase[-8:], "big"))
    wb = WorkingBlock(coinbase)
    assert isinstance(wb.rand_source, CoinbaseRand)
    for blk in range(1, 6):  # an advance, a build, an advance, ...
        assert wb.my_rand == rng.getrandbits(64)
        if blk % 2:  # not every height builds: the draws stay in step
            assert wb.rand_source.trust_rand(blk) == rng.getrandbits(64)
        wb.advance(blk + 1)


class Given:
    """A source that hands out what it was told to."""

    def __init__(self, seeds: dict):
        self.seeds, self.asked = seeds, []

    def my_rand(self, blk_num: int) -> int:
        self.asked.append(("my_rand", blk_num))
        return 1000 + blk_num

    def trust_rand(self, blk_num: int) -> int:
        self.asked.append(("trust_rand", blk_num))
        return self.seeds[blk_num]


def test_a_working_block_asks_the_source_it_was_given():
    src = Given({})
    wb = WorkingBlock(bytes(20), src)
    wb.advance(7)
    assert wb.my_rand == 1007
    assert src.asked == [("my_rand", 1), ("my_rand", 7)]


def test_with_a_given_source_each_heights_committee_is_the_references():
    """One proposer on the recording transport of the benchmark's driver,
    its source the generator's: block n carries the seed handed in, and
    the committee the node derives for height n + 1 is the reference's
    window over that seed, with the node in it."""
    from tests.test_proposer_path import DEPLOY, Rig

    rig = Rig(DEPLOY, seed=2**31 + 33)
    try:
        rig.seal(4)
        feed, node = rig.feed, rig.node
        for h in range(1, 6):
            seed = feed.seeds[h]
            assert node.trust_rands[h - 1] == seed
            if h > 1:
                blk = rig.chain.get_block_by_number(h - 1)
                assert blk.header.trust_rand == seed
            want = ref_members.committee(feed.members, seed, 0,
                                         DEPLOY["committee"])
            assert [m.addr for m in node.membership.committee(seed)] == want
            assert feed.node_addr in want and node.is_committee(h)
    finally:
        rig.close()
