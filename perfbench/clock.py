"""A wall clock with timers, for a ``TxPool`` that lives outside a node's
event loop: ``now()`` and ``call_later()`` as the pool uses them."""

from __future__ import annotations

import threading
import time


class ThreadClock:
    def now(self) -> float:
        return time.monotonic()

    def call_later(self, delay_s: float, fn):
        t = threading.Timer(delay_s, fn)
        t.daemon = True
        t.start()
        return t
