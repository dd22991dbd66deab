"""Controls: the program with one stated guarantee broken.  A run with
``--control <name>`` has to come out not correct; ``tests/`` keeps each as
a test and PERF.md gives the readings.

* ``accept_all`` (one-process deployments): the device verifier's answers
  with the validity mask forced true, so a row whose signature is no
  signature still yields a sender.  Breaks "an invalid signature yields no
  sender".
* ``short_cycle`` (one-process deployments): the run cycles through ONE
  block of frames and of votes, so the program's caches remember every row
  that comes again.  Breaks what makes a pass cost what fresh rows would;
  it is the control for ``cache_hit_share_pct``.
* ``one_lane`` (one-process deployments on several chips): the mesh
  verifier shows the scheduler the first of its devices alone, so one lane
  serves every window while the process holds all the chips.  Breaks the
  configuration's ``layout`` (one lane a chip); it is the control for
  ``lanes``.
* ``host_verifier`` (cluster deployments): the chip node runs the host
  verifier, so no sender is recovered on the device.  Breaks "the chip
  node's device rows carried the senders".
"""

from __future__ import annotations

import numpy as np


class AcceptAll:
    """A verifier facade around the real one that answers every row."""

    def __init__(self, inner):
        self._inner = inner
        self.max_batch = getattr(inner, "max_batch", None)

    def __getattr__(self, name):
        if name in ("stage_recover", "commit_recover", "collect_recover",
                    "device_targets"):
            raise AttributeError(name)  # the scheduler then calls inline
        return getattr(self._inner, name)

    def recover_addresses(self, sigs, hashes):
        addrs, ok = self._inner.recover_addresses(sigs, hashes)
        addrs = np.array(addrs)
        bad = ~np.asarray(ok, bool)
        addrs[bad, 0] |= 1  # some sender, never the null address
        return addrs, np.ones(len(addrs), bool)


class OneLane:
    """The real verifier, of whose devices the scheduler sees the first."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def device_targets(self) -> list:
        return self._inner.device_targets()[:1]
