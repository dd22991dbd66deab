"""Controls of the acceptor's deployment (``drivers/acceptor.py``): the
program with one stated guarantee broken.  A run with ``--control <name>``
has to come out not correct, each by the check that is its own;
``perfbench/tests/test_acceptor_cell.py`` keeps each as a test and PERF.md
gives the readings.  Each puts ITS kind of bad block first among the four
that come in turn (``first_bad``), so that a window of forty heights meets
it whatever the run's pace.

* ``accept_all``: the verifier answers every row valid.  A row whose
  signature is none gets the sender the generator MEANT it to have (by its
  signing hash, ``ChainFeed.meant``): some other address would fail at the
  nonce and the balance, which is the program's second line and not what
  this control is about.  Breaks guarantee (1), "an acceptor ACKs a block
  only if every transaction's signature yields its sender"; it is the
  control for ``bad_blocks_acked``.
* ``trust_roots``: the chain executes a block and compares none of the
  four commitments (state root, receipts root, gas used, bloom) with what
  the header says.  Breaks guarantee (1)'s other half; the control for
  ``bad_blocks_acked`` by a header that carries another block's state
  root.
* ``any_cert``: ``QuorumTally.cert_ok`` answers True whatever the
  supporters signed.  Breaks guarantee (2), "a block is inserted only
  under a certificate of valid signatures over THAT block's hash"; the
  control for ``bad_blocks_inserted``.
"""

from __future__ import annotations

import numpy as np

from perfbench.control import AcceptAll

NAMES = ("accept_all", "trust_roots", "any_cert")
FIRST_BAD = {None: "state_root", "accept_all": "signature",
             "trust_roots": "state_root", "any_cert": "certificate"}


class AcceptMeant(AcceptAll):
    """``control.AcceptAll`` whose answer for a row without a signature is
    the sender ``meant`` names for the row's signing hash, where it names
    one."""

    def __init__(self, inner, meant: dict):
        super().__init__(inner)
        self._meant = meant

    def recover_addresses(self, sigs, hashes):
        addrs, ok = self._inner.recover_addresses(sigs, hashes)
        addrs = np.array(addrs)
        for i in np.flatnonzero(~np.asarray(ok, bool)):
            want = self._meant.get(bytes(np.asarray(hashes[i], np.uint8)))
            if want is None:
                addrs[i, 0] |= 1  # some sender, never the null address
            else:
                addrs[i] = np.frombuffer(want, np.uint8)
        return addrs, np.ones(len(addrs), bool)


def verify_path_of(name, mode: str, meant: dict, **scheduler_kwargs):
    """``verify_path.build(mode, ...)`` with control ``name`` in place.
    ``meant`` is filled by the caller once the traffic is made."""
    from eges_tpu.crypto import verify_path

    bare = verify_path.build(mode, **scheduler_kwargs)
    if name != "accept_all":
        return bare
    bare.verifier.close()  # the facade gets a scheduler of its own
    return verify_path.on_scheduler(
        verify_path.VerifyPath(mode, raw=AcceptMeant(bare.raw, meant),
                               platform=bare.platform), **scheduler_kwargs)


def chain_class(name):
    """``BlockChain``, or under ``trust_roots`` one that compares no
    commitment."""
    from eges_tpu.core.chain import BlockChain, ChainError

    if name != "trust_roots":
        return BlockChain

    class TrustRoots(BlockChain):
        def _process(self, block, parent_state):
            from eges_tpu.core.state import (StateError, process_block,
                                             recover_senders)
            try:
                senders = recover_senders(block.transactions, self.verifier)
                return process_block(parent_state, block, senders,
                                     self.verifier)
            except StateError as e:
                raise ChainError(str(e))

    return TrustRoots


def quorum_of(name, node):
    """The node's quorum arithmetic, or under ``any_cert`` one whose
    certificate check always passes."""
    if name != "any_cert":
        return node.quorum

    class AnyCert(type(node.quorum)):
        def cert_ok(self, confirm, seed) -> bool:
            return True

    q = node.quorum
    return AnyCert(q.membership, q.verifier, signing=q.signing, now=q._now)
