"""Controls of the validator's deployment (``drivers/validator.py``): the
program with one stated guarantee, or one premise of the cell, broken.  A
run with ``--control <name>`` has to come out not correct, each by the
check that is its own; ``tests/`` keeps each as a test and PERF.md gives
the readings.

* ``no_cache``: the scheduler is built with ``cache_size=0``, so the
  recovery cache answers nothing and every row that gossip brought is
  recovered again when its block arrives.  Breaks the cell's premise (half
  of what enters the scheduler is answered without the device); it is the
  control for the lower limit of ``cache_hit_share_pct``.
* ``short_cycle``: the run cycles through ONE block, so the pool's hash
  history stops every gossip frame and the cache answers every block row.
  Breaks what makes a pass cost what fresh rows would; it is the control
  for the upper limit of ``cache_hit_share_pct``.
* ``accept_all``: ``control.AcceptAll``, the device verifier's answers
  with the validity mask forced true: a transaction whose signature is no
  signature yields a sender, so a bad block passes.  Breaks "a block that
  holds one is refused whole"; it is the control for
  ``bad_blocks_not_refused``.
"""

from __future__ import annotations

from perfbench.control import AcceptAll

NAMES = ("no_cache", "short_cycle", "accept_all")


def apply(name, raw, d: dict) -> tuple:
    """``(verifier, deployment, scheduler keywords)`` with control
    ``name`` in place (None: as they are)."""
    if name is None:
        return raw, d, {}
    if name == "no_cache":
        return raw, d, {"cache_size": 0}
    if name == "short_cycle":
        return raw, {**d, "pool_blocks": 1}, {}
    if name == "accept_all":
        return AcceptAll(raw), d, {}
    raise SystemExit(f"no control {name!r} for this driver")
