"""Controls of the proposer's deployment (``drivers/proposer.py``): the
program with one stated guarantee, or one premise of the cell, broken.  A
run with ``--control <name>`` has to come out not correct, each by the
check that is its own; ``tests/`` keeps each as a test and PERF.md gives
the readings.

* ``accept_all``: the tally runs with signed votes OFF (upstream's
  trustedHW mode), so every collected reply counts unverified and the
  three forged ACKs among a block's first 169 become supporters.  Breaks
  "a forged ACK never counts"; it is the control for
  ``forged_supporters``.
* ``majority``: the chain's ``validate_threshold`` is left out, so the
  program certifies on upstream's majority of 129 where the deployment
  asks for 169.  Breaks "a quorum is certified only on at least 169
  ACKs"; it is the control for ``supporters_under_threshold``.
* ``short_cycle``: the run cycles through ONE block of votes, so the
  recovery cache remembers every vote row that comes again (a first
  attempt's 169 rows too).  Breaks what makes a pass cost what fresh
  rows would; it is the control for ``cache_hit_share_pct``.
"""

from __future__ import annotations

NAMES = ("accept_all", "majority", "short_cycle")


def apply(name, d: dict) -> tuple:
    """``(deployment, the chain's validate_threshold, signed votes)`` with
    control ``name`` in place (None: as they are)."""
    fraction = d["validate_threshold"]
    if name is None:
        return d, fraction, True
    if name == "accept_all":
        return d, fraction, False
    if name == "majority":
        return d, None, True
    if name == "short_cycle":
        return {**d, "vote_pool_blocks": 1}, fraction, True
    raise SystemExit(f"no control {name!r} for this driver")
