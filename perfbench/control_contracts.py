"""Controls of the Smallbank deployment (``drivers/smallbank.py``): the
program with one stated guarantee broken.  A run with ``--control <name>``
has to come out not correct, each by the check that is its own;
``perfbench/tests/test_smallbank_cell.py`` keeps each as a test and
PERF.md gives the readings.

* ``keep_reverted``: a frame that ends in REVERT has its storage writes
  absorbed into the state all the same, and the chain compares no
  commitment (with them compared it would refuse the first block that
  holds an aborted ``sendPayment``, and a chain that stands still fails
  by every check there is).  Breaks guarantee (8), "a call that aborts
  leaves no write": the payee of an aborted payment keeps the credit.
  The control for ``contract_state_wrong``.  Its bad blocks begin with
  the three kinds that no commitment decides (``signature``,
  ``nonce_gap``, ``certificate``: heights 8, 24 and 40), so a window
  that stays under height 56 meets no other check.
* ``accept_all``: ``control_accept``'s, the verifier that answers every
  row valid with the sender the generator meant.  Breaks guarantee (1);
  the control for ``bad_blocks_acked``.
"""

from __future__ import annotations

from perfbench import control_accept

NAMES = ("keep_reverted", "accept_all")
FIRST_BAD = {None: "state_root", "keep_reverted": "signature",
             "accept_all": "signature"}

verify_path_of = control_accept.verify_path_of


def chain_class(name):
    """``BlockChain``, or under ``keep_reverted`` one that compares no
    commitment and executes over an interpreter that keeps a reverted
    frame's writes (for the length of an execution: nothing of the
    program stays changed)."""
    if name != "keep_reverted":
        return control_accept.chain_class(None)
    from eges_tpu.core import evm

    rolled_back = evm.EVM._finish_revert

    def kept(self, task, r):
        res = rolled_back(self, task, r)
        task.frame_state.set_storage_many(task.to, task.frame.swrites)
        task.snapshot.absorb(task.frame_state)
        return res

    class KeepReverted(control_accept.chain_class("trust_roots")):
        def _process(self, block, parent_state):
            evm.EVM._finish_revert = kept
            try:
                return super()._process(block, parent_state)
            finally:
                evm.EVM._finish_revert = rolled_back

    return KeepReverted
