"""Traffic of co-hosted validators: what EACH of ``nodes`` node processes
on one host receives of the ONE chain they all follow
(``drivers/sidecar.py``).  It wraps ``gen.NodeFeed`` and edits nothing
there: the keys, frames, vote rows and invalid rows are that feed's, made
from ``--seed`` alone, so every node of a run holds the same blocks.

What differs from node to node, seeded by seed AND node:

* the order in which a block's frames arrive.  Gossip reaches each
  validator along its own paths, so node ``i`` gets block ``b``'s
  ``txn_per_block`` frames in an order of its own, by ``NodeFeed``'s rule
  (a head of frames once, then the rest mixed with re-gossiped copies of
  the node's own first frames, cut into gossip windows): the same distinct
  frames at every node, the copies each node's own;
* who tallies the ACKs.  A block's election rows and its header row reach
  every node; its ACK replies reach ONE, the block's proposer, and the
  co-hosted validators propose in turn (block ``b`` is node ``b % nodes``'s).

So a block is, by construction (``construction()``, held by a test),
``nodes * (txn_per_block + committee + header_sigs) + validators`` rows
asked of the nodes (13,123 at the source's sizes: 3 x 4000 + 3 x 32 + 3 x
1 + 1024) where one node alone is asked 5057.  Each node's pool stops its
own copies at its dedup, so the rows that reach the SIDECAR are
``nodes * (unique frames + committee + header_sigs) + validators``
(10,123) for ``unique frames + committee + header_sigs + validators``
(4,057) distinct signature keys: 59.9% of what the sidecar is asked is a
key another node asks within the same block.
"""

from __future__ import annotations

import random

from perfbench import gen


class SharedFeed:
    """Node ``node``'s share of the chain ``gen.NodeFeed(seed, d)``
    describes.  Everything the node driver's ``Tally`` and ``Node`` read
    of a feed is the inner feed's; ``windows`` and ``votes`` are this
    node's own.  ``inner`` shares one feed among the nodes of one
    process."""

    def __init__(self, seed: int, node: int, d: dict, inner=None):
        self.inner = inner if inner is not None else gen.NodeFeed(seed, d)
        self.node, self.nodes = node, d["nodes"]
        feed = self.inner
        rng = random.Random((seed << 8) ^ (0xC0705 + node))
        uniq, dups, w = feed.uniq, feed.dups, d["gossip_window"]
        head = min(uniq, dups + w)
        marks = [False] * (uniq - head) + [True] * dups
        self.blocks = []
        for b in range(d["pool_blocks"]):
            order = list(range(b * uniq, (b + 1) * uniq))
            rng.shuffle(order)
            rng.shuffle(marks)
            again = order[:dups]
            rng.shuffle(again)
            seq, nxt = order[:head], head
            for is_dup in marks:
                if is_dup:
                    seq.append(again.pop())
                else:
                    seq.append(order[nxt])
                    nxt += 1
            self.blocks.append([seq[i:i + w] for i in range(0, len(seq), w)])

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def windows(self, block: int) -> list:
        """Block ``block``'s gossip windows in this node's order."""
        return self.blocks[block % len(self.blocks)]

    def proposes(self, block: int) -> bool:
        return block % self.nodes == self.node

    def votes(self, block: int):
        """``(election, header, ack)`` vote-row indices; the ACK replies
        only where this node proposes the block."""
        el, hd, ack = self.inner.votes(block)
        return el, hd, ack if self.proposes(block) else range(0)

    def construction(self) -> dict:
        """A block's rows by construction, for the host and the sidecar."""
        d, feed = self.inner.d, self.inner
        small = d["committee"] + d["header_sigs"]
        asked = self.nodes * (d["txn_per_block"] + small) + d["validators"]
        at_sidecar = self.nodes * (feed.uniq + small) + d["validators"]
        keys = feed.uniq + small + d["validators"]
        return {"rows_asked": asked,
                "rows_one_node": d["txn_per_block"] + small
                + d["validators"],
                "sidecar_rows_asked": at_sidecar,
                "sidecar_keys": keys,
                "shared_share_pct": 100.0 * (1.0 - keys / at_sidecar)}
