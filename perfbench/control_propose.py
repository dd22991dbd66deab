"""Controls of the block proposer's deployment
(``drivers/block_proposer.py``): the program with one stated guarantee
broken.  A run with ``--control <name>`` has to come out not correct, each
by the check that is its own and by no other;
``perfbench/tests/test_proposer_cell.py`` and
``tests/test_proposer_path.py`` keep each as a test and PERF.md gives the
readings.

* ``accept_all``: the ACK tally counts every collected reply as verified
  (its attempt hands nothing to the verifier), so the forged ACKs among a
  height's first 513 become supporters and their signatures go into the
  confirm.  Breaks guarantee (4), "no forged reply among a certificate's
  supporters"; the control for ``forged_supporters``.  Elections and
  gossip keep the real verifier.
* ``pad_upstream``: the proposal is padded as upstream pads it, with
  ``txn_per_block`` less the UDP transactions unsigned 100 B fakes BESIDE
  a full pool's 4000 transfers: a request of about 1.3 MB.  Breaks
  guarantee (3), "every validate request is at most 1,048,576 bytes"; the
  control for ``request_bytes_max``.  (Every acceptor of this program
  would drop such a request unread; the generator answers it all the
  same, so that the run has a line to judge.)
* ``unfiltered_pool``: the pool hands the proposer every pending
  transaction in nonce order, without the cut at the state's nonce, a gap
  or the balance.  The preview drops what cannot execute and the block
  goes out short.  Breaks guarantee (2), "a full pool gives a full
  block"; the control for ``blocks_not_full``.  It puts the first
  unexecutable transfers into the chain's first stream
  (:data:`FIRST_UNEXECUTABLE`), so that every window meets them.
"""

from __future__ import annotations

NAMES = ("accept_all", "pad_upstream", "unfiltered_pool")
FIRST_UNEXECUTABLE = {None: None, "accept_all": None, "pad_upstream": None,
                      "unfiltered_pool": 0}


def node_class(name):
    """``GeecNode``, or under ``pad_upstream`` one that pads a proposal to
    ``txn_per_block`` fakes whatever its signed transactions number."""
    from eges_tpu.consensus.node import GeecNode

    if name != "pad_upstream":
        return GeecNode

    class PadUpstream(GeecNode):
        def _assemble_proposal(self, blk_num: int):
            from eges_tpu.core.types import fake_txn, new_block

            blk = super()._assemble_proposal(blk_num)
            fakes = tuple(fake_txn(self.cfg.txn_size, seq=i) for i in range(
                self.cfg.txn_per_block - len(blk.geec_txns)))
            return new_block(blk.header, txs=blk.transactions,
                             geec_txns=blk.geec_txns, fake_txns=fakes)

    return PadUpstream


def quorum_of(name, node):
    """The node's quorum arithmetic, or under ``accept_all`` one whose ACK
    attempts verify nothing."""
    if name != "accept_all":
        return node.quorum

    class AcceptAllAcks(type(node.quorum)):
        def attempt(self, wb, kind, entries, need):
            if kind != "ack":
                return super().attempt(wb, kind, entries, need)
            out: dict = {}
            for a, _h, s in entries:
                out.setdefault(a, s)
            return out

    q = node.quorum
    return AcceptAllAcks(q.membership, q.verifier, signing=q.signing,
                         now=q._now)


def pool_class(name):
    """``TxPool``, or under ``unfiltered_pool`` one whose pending run is
    not cut to what executes."""
    from eges_tpu.core.txpool import TxPool

    if name != "unfiltered_pool":
        return TxPool

    class Unfiltered(TxPool):
        def pending_txns(self, limit=None, state=None):
            return super().pending_txns(limit, None)

    return Unfiltered
